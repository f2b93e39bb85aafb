"""The contract lints and the per-target analysis drivers.

Finding codes (see ``docs/static_analysis.md``):

=====  ========================================================
SA101  write (or init key) outside the scheme's own state slice
SA102  read of a forbidden shared / foreign state field
SA201  integer value carried through a float dtype too narrow
       to represent it exactly (the 2**24 float32 index bug)
SA202  state leaf changes dtype/shape/weak-type across a tick
SA301  class output not provably inside [0, n_classes)
SA302  class output dtype is not int32
SA401  host callback / effectful primitive in a traced body
SA501  cross-volume mixing: a carried state leaf depends on
       another volume's data (volume axis reduced / gathered /
       contracted outside the summarization allowlist)
SA502  collective primitive over the fleet mesh axis inside
       the sharded body
SA503  donation / aliasing hazard (buffer aliased into two
       outputs, or a donated buffer read after the donating call)
SA504  volume-axis rank/extent drift, or the volume axis moved
       off dim 0, on a state leaf across the tick boundary
=====  ========================================================
"""

from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp
import numpy as np

from repro.core.placement import registry

from . import provenance, tracing
from .intervals import FLOAT_EXACT_INT, IntervalAnalysis
from .manifest import state_manifest
from .walker import collective_axes, impurity_of, is_literal, iter_eqns, subjaxprs

CODES = {
    "SA101": "cross-slice state write",
    "SA102": "forbidden shared-field read",
    "SA201": "float index carry",
    "SA202": "state dtype/shape drift across tick",
    "SA301": "class id not provably in [0, n_classes)",
    "SA302": "class output dtype is not int32",
    "SA401": "effectful primitive / host callback",
    "SA501": "cross-volume state mixing",
    "SA502": "collective over the fleet mesh axis",
    "SA503": "donation / aliasing hazard",
    "SA504": "volume-axis drift across the tick",
}

# The fleet mesh axis name `core/fleetshard.py` shards volumes over.
FLEET_AXIS = "fleet"

# Every fleet entry point the SA5xx battery covers, in trace order; the
# JSON report carries this list so CI can assert coverage, and
# `analyze_fleet` asserts it stays in sync with `tracing.fleet_traces`.
FLEET_TRACE_LABELS = ("fleet.step", "fleet.gc_tick", "fleet.body",
                      "fleet.shard_body")

# Summarization allowlist for SA501: carried state keys that are *declared*
# fleet-level aggregates, allowed to blend data across volumes. Empty today
# — the one legitimate cross-volume reduction in the engine
# (`fleet_gc_tick`'s `jnp.any(need)`) feeds only the GC loop predicate and
# never reaches a state output, so the reachability formulation admits it
# with no entry here. A future deliberate fleet summary (say a global free
# -pool gauge) earns its key a place on this list, nothing else does.
FLEET_SUMMARY_ALLOWLIST = frozenset()

# Shared engine fields a scheme may read (never write): the clock, the ℓ
# estimate, and the per-LBA location/last-write tables the paper's schemes
# key their decisions on. Everything else — segment metadata, counters,
# policy scalars, other schemes' sch_* slices — is off limits.
ALLOWED_SHARED_READS = frozenset({"t", "ell", "loc_seg", "loc_off",
                                  "last_uw"})


@dataclasses.dataclass(frozen=True)
class Finding:
    code: str
    where: str              # entry point, e.g. "dac.user_class"
    message: str

    def __str__(self):
        return f"{self.code} [{self.where}] {self.message}"

    def as_dict(self):
        return {"code": self.code, "kind": CODES[self.code],
                "where": self.where, "message": self.message}


def _dedup(findings):
    return list(dict.fromkeys(findings))


# -- individual lints ----------------------------------------------------------

def lint_slice_isolation(rec, prefix):
    """SA101/SA102 from the read/write manifest."""
    m = state_manifest(rec)
    out = []
    for key in m.writes:
        if not key.startswith(prefix):
            out.append(Finding(
                "SA101", rec.label,
                f"writes state key {key!r} outside its own slice "
                f"(allowed prefix {prefix!r})"))
    for key in m.reads:
        if key.startswith(prefix) or key in ALLOWED_SHARED_READS:
            continue
        what = ("another scheme's slice" if key.startswith("sch_")
                else "a forbidden shared field")
        out.append(Finding("SA102", rec.label,
                           f"reads {what}: {key!r}"))
    return out, m


def lint_drift(rec):
    """SA202: the carried state pytree must map exactly onto itself."""
    out = []
    for key, i in rec.state_in.items():
        j = rec.state_out.get(key)
        if j is None:
            out.append(Finding("SA202", rec.label,
                               f"state key {key!r} dropped from the "
                               "carried pytree"))
            continue
        a = rec.jaxpr.invars[i].aval
        b = rec.jaxpr.outvars[j].aval
        diffs = []
        if a.dtype != b.dtype:
            diffs.append(f"dtype {a.dtype} -> {b.dtype}")
        if a.shape != b.shape:
            diffs.append(f"shape {a.shape} -> {b.shape}")
        if bool(getattr(a, "weak_type", False)) != bool(
                getattr(b, "weak_type", False)):
            diffs.append("weak-type flag flips")
        if diffs:
            out.append(Finding(
                "SA202", rec.label,
                f"state key {key!r} changes across the tick boundary: "
                + "; ".join(diffs)))
    for key in rec.state_out:
        if key not in rec.state_in:
            out.append(Finding("SA202", rec.label,
                               f"state key {key!r} appears only on the "
                               "output side of the tick"))
    return out


def run_interval_lints(rec):
    """One interval pass collecting SA201/SA401; returns (findings,
    out_intervals aligned with the jaxpr's outvars)."""
    found = []

    def visit(eqn, ins):
        reason = impurity_of(eqn)
        if reason is not None:
            found.append(Finding("SA401", rec.label,
                                 f"impure operation: {reason}"))
        if eqn.primitive.name != "convert_element_type":
            return
        new = eqn.params.get("new_dtype")
        src = getattr(eqn.invars[0].aval, "dtype", None)
        if new is None or src is None:
            return
        if not (jnp.issubdtype(new, jnp.integer)
                and jnp.issubdtype(src, jnp.floating)):
            return
        try:
            src_name = np.dtype(src).name
        except TypeError:
            src_name = str(src)
        limit = FLOAT_EXACT_INT.get(src_name, 2.0 ** 24)
        lo, hi = ins[0]
        if lo < -limit or hi > limit:
            span = ("unbounded" if not (math.isfinite(lo)
                                        and math.isfinite(hi))
                    else f"[{lo:g}, {hi:g}]")
            found.append(Finding(
                "SA201", rec.label,
                f"integer value cast {src} -> {np.dtype(new).name} with "
                f"range {span}, beyond the exact-integer window "
                f"±{limit:g} of {src}"))

    out_ivs = IntervalAnalysis(visitor=visit).run(rec.closed_jaxpr,
                                                  rec.seeds)
    return found, out_ivs


def lint_totality(rec, out_intervals, n_classes):
    """SA301/SA302 on the class output slot."""
    out = []
    slot = rec.class_out
    if slot is None:
        return out
    aval = rec.jaxpr.outvars[slot].aval
    if np.dtype(aval.dtype) != np.int32:
        out.append(Finding("SA302", rec.label,
                           f"class output dtype is {aval.dtype}, "
                           "expected int32"))
    lo, hi = out_intervals[slot]
    if not (lo >= 0 and hi <= n_classes - 1):
        span = ("unbounded" if not (math.isfinite(lo) and math.isfinite(hi))
                else f"[{lo:g}, {hi:g}]")
        out.append(Finding(
            "SA301", rec.label,
            f"class output interval is {span}, not provably inside "
            f"[0, {n_classes})"))
    return out


def lint_volume_isolation(rec, n_volumes=None):
    """SA501/SA504 from the batch-axis provenance pass: every carried state
    output leaf must keep the volume axis intact at dim 0 (or be a fresh
    volume-uniform value). ``Mixed`` provenance is cross-volume mixing
    (SA501) unless the key sits on :data:`FLEET_SUMMARY_ALLOWLIST`; an axis
    that moved, or a rank/extent change on the volume axis, is SA504."""
    out = []
    provs = provenance.ProvenanceAnalysis().run(
        rec.closed_jaxpr, provenance.volume_seeds(rec.closed_jaxpr))
    for key, j in sorted(rec.state_out.items()):
        p = provs[j]
        if p.kind == "mixed" and key not in FLEET_SUMMARY_ALLOWLIST:
            out.append(Finding(
                "SA501", rec.label,
                f"state key {key!r} mixes data across the volume axis "
                f"(via {p.origin}): one volume's carried state depends on "
                "another's"))
        elif p.kind == "axis" and p.dim != 0:
            out.append(Finding(
                "SA504", rec.label,
                f"state key {key!r} comes out with the volume axis moved "
                f"to dim {p.dim} (expected the leading dim)"))
    for key, i in rec.state_in.items():
        j = rec.state_out.get(key)
        if j is None:
            continue                      # lint_drift's SA202 territory
        a = rec.jaxpr.invars[i].aval
        b = rec.jaxpr.outvars[j].aval
        if len(a.shape) != len(b.shape) or a.shape[:1] != b.shape[:1]:
            out.append(Finding(
                "SA504", rec.label,
                f"state key {key!r} drifts on the volume axis across the "
                f"tick boundary: {a.shape} -> {b.shape}"))
    if not rec.state_in and n_volumes is not None:
        for key, j in sorted(rec.state_out.items()):
            b = rec.jaxpr.outvars[j].aval
            if len(b.shape) == 0 or b.shape[0] != n_volumes:
                out.append(Finding(
                    "SA504", rec.label,
                    f"state key {key!r} lost its leading volume axis: "
                    f"final shape {b.shape}, expected ({n_volumes}, ...)"))
    return out


def lint_collectives(rec, axis_name=FLEET_AXIS):
    """SA502: any collective communication primitive over the fleet mesh
    axis, anywhere in the traced program (shard_map body included)."""
    out = []
    for eqn in iter_eqns(rec.jaxpr):
        axes = collective_axes(eqn)
        if axis_name in axes:
            out.append(Finding(
                "SA502", rec.label,
                f"collective {eqn.primitive.name!r} over mesh axis "
                f"{axis_name!r}: volumes are independent logs, the sharded "
                "body must be collective-free"))
    return _dedup(out)


def lint_donation(rec):
    """SA503 donation/aliasing hazards in the tick program: one input
    buffer aliased into two output slots (donating it would leave two live
    state leaves sharing storage), or a donated operand consumed again
    after the donating call (use-after-free under donation)."""
    jaxpr = rec.jaxpr
    out = []
    key_of_slot = {j: k for k, j in rec.state_out.items()}
    invars = set(jaxpr.invars)
    slots_by_var = {}
    for j, atom in enumerate(jaxpr.outvars):
        if not is_literal(atom) and atom in invars:
            slots_by_var.setdefault(atom, []).append(j)
    for slots in slots_by_var.values():
        if len(slots) > 1:
            keys = sorted(str(key_of_slot.get(j, f"out[{j}]"))
                          for j in slots)
            out.append(Finding(
                "SA503", rec.label,
                "one input buffer is aliased into multiple output slots "
                f"({', '.join(keys)}): donating it would alias two live "
                "state leaves"))
    out += _donated_reuse(jaxpr, rec.label)
    return _dedup(out)


def _donated_reuse(jaxpr, label):
    """Donated jit operands / pallas_call aliased operands read after the
    donating equation, at any jaxpr nesting level."""
    findings = []
    for idx, eqn in enumerate(jaxpr.eqns):
        name = eqn.primitive.name
        donated = ()
        if name == "jit":
            donated = eqn.params.get("donated_invars", ())
        elif name == "pallas_call":
            aliases = dict(eqn.params.get("input_output_aliases", ()) or ())
            donated = tuple(i in aliases for i in range(len(eqn.invars)))
        for var, give in zip(eqn.invars, donated):
            if not give or is_literal(var):
                continue
            used_later = any(
                any(a is var for a in later.invars if not is_literal(a))
                for later in jaxpr.eqns[idx + 1:])
            escapes = any(o is var for o in jaxpr.outvars)
            if used_later or escapes:
                findings.append(Finding(
                    "SA503", label,
                    f"buffer donated to a {name!r} call is read again "
                    "afterwards — a use-after-free once donation is "
                    "honored"))
        for sub, _ in subjaxprs(eqn):
            findings += _donated_reuse(sub, label)
    return findings


# -- per-target drivers --------------------------------------------------------

def analyze_scheme(cfg, name, n_classes, impl):
    """All lints for one JaxPlacement triple (registered or fixture).
    Returns (findings, {entry: Manifest})."""
    findings, manifests = [], {}
    try:
        registry.check_jax_state_slice(name, impl, cfg)
    except AssertionError as exc:
        findings.append(Finding("SA101", f"{name}.init_state", str(exc)))
    prefix = registry.slice_prefix(name)
    for rec in tracing.scheme_traces(cfg, name, impl):
        iso, m = lint_slice_isolation(rec, prefix)
        manifests[rec.label.split(".", 1)[1]] = m
        findings += iso
        findings += lint_drift(rec)
        interval_findings, out_ivs = run_interval_lints(rec)
        findings += interval_findings
        findings += lint_totality(rec, out_ivs, n_classes)
    return _dedup(findings), manifests


def analyze_engine(cfg):
    """Drift + overflow + purity over one full engine user step."""
    rec = tracing.engine_trace(cfg)
    findings = lint_drift(rec)
    interval_findings, _ = run_interval_lints(rec)
    return _dedup(findings + interval_findings)


def analyze_kernels():
    """Overflow + purity over the kernel entry points; returns
    {label: findings}."""
    out = {}
    for rec in tracing.kernel_traces():
        findings, _ = run_interval_lints(rec)
        out[rec.label] = _dedup(findings)
    return out


def analyze_fleet(cfg, n_volumes=4, horizon=6, mesh=None):
    """The SA5xx battery over the fleet engine: provenance + donation over
    the vmapped tick (`fleet_step`, `fleet_gc_tick`) and the whole replay
    (`fleet_body`), plus the collective scan over the exact
    ``shard_map(fleet_body)`` program `_sharded_runner` jits."""
    findings, labels = [], []
    for rec in tracing.fleet_traces(cfg, n_volumes=n_volumes,
                                    horizon=horizon):
        labels.append(rec.label)
        findings += lint_volume_isolation(rec, n_volumes=n_volumes)
        findings += lint_donation(rec)
        findings += lint_collectives(rec)
    shard = tracing.fleet_shard_trace(cfg, n_volumes=n_volumes,
                                      horizon=horizon, mesh=mesh)
    labels.append(shard.label)
    assert tuple(labels) == FLEET_TRACE_LABELS, labels
    findings += lint_collectives(shard)
    findings += lint_volume_isolation(shard)
    return _dedup(findings)


def analyze_fleet_fixture(cfg, fx, n_volumes=4):
    """The same SA5xx battery over one fleet violation fixture."""
    rec = tracing.fleet_fixture_trace(cfg, fx, n_volumes=n_volumes)
    findings = lint_volume_isolation(rec, n_volumes=n_volumes)
    findings += lint_donation(rec)
    findings += lint_collectives(rec)
    return _dedup(findings)
