"""Batch-axis provenance over jaxprs: is the volume axis intact?

The fleet engine's whole deployment story — ``shard_map`` over a device
mesh today, a `jax.distributed` pod slice tomorrow — rests on one
invariant: **no volume's carried state ever depends on another volume's**.
This pass proves it statically. Every jaxpr value is abstracted to one of
three provenance facts about the leading volume axis:

* ``NONE`` — the value carries no per-volume data (a scalar clock bound,
  a broadcast constant, an iota): uniform across the fleet.
* ``Axis(d)`` — the value has the volume axis *intact* at dimension ``d``;
  element ``v`` along that axis is a function of volume ``v``'s inputs
  only.
* ``Mixed(origin)`` — the volume axis was reduced, gathered, permuted or
  otherwise contracted: the value blends data from multiple volumes.
  ``origin`` names the primitive that first mixed it.

The transfer rules track the axis through reshapes/transposes/broadcasts,
keep it across *per-volume* reductions (``axes`` not containing the volume
dim), recurse precisely through ``jit``/``cond``/``switch``/``shard_map``
and run carry fixpoints for ``scan``/``while``. Batched ``gather`` /
``scatter`` use the ``operand_batching_dims`` bookkeeping vmap emits: a
volume may index freely *within its own row*, never across rows. Any
primitive without a rule is conservatively ``Mixed`` when fed per-volume
data — soundness over precision.

The lint layer (SA501/SA504 in ``lints.py``) then checks the facts at the
tick boundary: a carried state leaf must come out ``Axis(0)`` (or
``NONE``, for a freshly broadcast uniform value). ``Mixed`` reaching state
is cross-volume mixing (SA501) unless the key is allowlisted as a
deliberate fleet summary; an axis that *moved* (``Axis(d != 0)``) is
volume-axis drift (SA504). Reductions that feed only a loop predicate —
``fleet_gc_tick``'s ``jnp.any(need)`` — never reach state outputs, so the
formulation allows them structurally, with no special case.
"""

from __future__ import annotations

import dataclasses

from .walker import is_literal, subjaxprs

_MAX_FIXPOINT_ITERS = 8  # lattice height is 3; this is pure paranoia


@dataclasses.dataclass(frozen=True)
class Prov:
    """Provenance of one jaxpr value w.r.t. the volume axis."""

    kind: str                 # "none" | "axis" | "mixed"
    dim: int | None = None    # for "axis": which dimension is the V axis
    origin: str | None = None  # for "mixed": primitive that first mixed

    def __repr__(self):
        if self.kind == "axis":
            return f"Axis({self.dim})"
        if self.kind == "mixed":
            return f"Mixed({self.origin})"
        return "NONE"


NONE = Prov("none")


def axis(d: int) -> Prov:
    return Prov("axis", dim=int(d))


def mixed(origin: str) -> Prov:
    return Prov("mixed", origin=origin)


def join(a: Prov, b: Prov) -> Prov:
    """Least upper bound: NONE < Axis(d) < Mixed. Two different axis dims
    join to Mixed (the value conflates two placements of the volume axis)."""
    if a.kind == "mixed":
        return a
    if b.kind == "mixed":
        return b
    if a.kind == "none":
        return b
    if b.kind == "none":
        return a
    if a.dim == b.dim:
        return a
    return mixed(f"axis join {a.dim}/{b.dim}")


def _tainted(provs, name):
    """Mixed if any input is; the per-rule fallthrough for taint."""
    for p in provs:
        if p.kind == "mixed":
            return p
        if p.kind == "axis":
            return mixed(name)
    return NONE


# -- shape-indexed rule helpers ------------------------------------------------

def _reduce_axes(p: Prov, axes, name):
    """A reduction over ``axes``: mixing iff the volume dim is reduced;
    otherwise the axis index shifts down past the removed dims."""
    if p.kind != "axis":
        return p
    axes = tuple(axes)
    if p.dim in axes:
        return mixed(name)
    return axis(p.dim - sum(1 for a in axes if a < p.dim))


def _reshape_dim(in_shape, out_shape, d):
    """Output dim the volume axis lands on, when the reshape provably keeps
    it whole: the element-count prefix before it and its own extent must
    both be preserved. Returns None when unprovable."""
    def prod(xs):
        n = 1
        for x in xs:
            n *= int(x)
        return n

    before = prod(in_shape[:d])
    for dd in range(len(out_shape)):
        if prod(out_shape[:dd]) == before and out_shape[dd] == in_shape[d]:
            return dd
    return None


def _gather_batch_pos(dnums, indices_rank, b):
    """Output dim that start_indices dim ``b`` maps to: the b'-th output
    batch dim, where b' is b's ordinal among non-index-vector dims. (JAX's
    gather fixes the index-vector dim as the last start_indices dim.)"""
    batch_src = [i for i in range(indices_rank - 1)]
    if b not in batch_src:
        return None
    ordinal = batch_src.index(b)
    out_rank = len(dnums.offset_dims) + len(batch_src)
    out_batch = [i for i in range(out_rank) if i not in dnums.offset_dims]
    return out_batch[ordinal] if ordinal < len(out_batch) else None


class ProvenanceAnalysis:
    """One pass over a closed jaxpr computing per-output provenance.

    ``run(closed_jaxpr, in_provs)`` returns provenances aligned with the
    jaxpr's outvars. Constants are ``NONE`` (weight tables and literals are
    volume-uniform by construction)."""

    def run(self, closed_jaxpr, in_provs):
        jaxpr = closed_jaxpr.jaxpr
        return self._jaxpr(jaxpr, [NONE] * len(jaxpr.constvars),
                           list(in_provs))

    # -- core walk -------------------------------------------------------------

    def _atom(self, atom, env):
        if is_literal(atom):
            return NONE
        return env.get(atom, NONE)

    def _jaxpr(self, jaxpr, const_provs, in_provs):
        env = {}
        for var, p in zip(jaxpr.constvars, const_provs):
            env[var] = p
        for var, p in zip(jaxpr.invars, in_provs):
            env[var] = p
        for eqn in jaxpr.eqns:
            ins = [self._atom(a, env) for a in eqn.invars]
            outs = self._eqn(eqn, ins)
            for var, p in zip(eqn.outvars, outs):
                env[var] = p
        return [self._atom(v, env) for v in jaxpr.outvars]

    # -- transfer rules --------------------------------------------------------

    def _eqn(self, eqn, ins):
        name = eqn.primitive.name
        n_out = len(eqn.outvars)

        if name in ("jit", "closed_call", "core_call", "custom_jvp_call",
                    "custom_vjp_call", "custom_vjp_call_jaxpr",
                    "remat_call", "checkpoint"):
            inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            if inner is not None and hasattr(inner, "jaxpr"):
                return self._jaxpr(inner.jaxpr,
                                   [NONE] * len(inner.jaxpr.constvars),
                                   list(ins))
            return self._unknown(eqn, ins)

        if name == "shard_map":
            # per-shard view: the volume axis stays at the same dim, only
            # its extent shrinks; recurse into the body one-to-one
            inner = eqn.params.get("jaxpr")
            if inner is not None:
                body = getattr(inner, "jaxpr", inner)
                return self._jaxpr(body, [NONE] * len(body.constvars),
                                   list(ins))
            return self._unknown(eqn, ins)

        if name == "cond":  # also lax.switch: N branches, same signature
            outs = None
            for br in eqn.params["branches"]:
                got = self._jaxpr(br.jaxpr, [NONE] * len(br.jaxpr.constvars),
                                  list(ins[1:]))
                # a per-volume predicate selecting between branch results
                # taints them: the branch taken depends on which volume
                got = [join(p, ins[0]) if ins[0].kind != "none" else p
                       for p in got]
                outs = got if outs is None else [join(a, b)
                                                for a, b in zip(outs, got)]
            return outs if outs is not None else [NONE] * n_out

        if name == "while":
            return self._while(eqn, ins)
        if name == "scan":
            return self._scan(eqn, ins)

        if name == "broadcast_in_dim":
            p = ins[0]
            if p.kind != "axis":
                return [p]
            bdims = eqn.params["broadcast_dimensions"]
            return [axis(bdims[p.dim])]

        if name in ("reshape", "squeeze", "expand_dims"):
            p = ins[0]
            if p.kind != "axis":
                return [p]
            in_shape = eqn.invars[0].aval.shape
            out_shape = eqn.outvars[0].aval.shape
            d = _reshape_dim(in_shape, out_shape, p.dim)
            return [axis(d) if d is not None else mixed(name)]

        if name == "transpose":
            p = ins[0]
            if p.kind != "axis":
                return [p]
            perm = list(eqn.params["permutation"])
            return [axis(perm.index(p.dim))]

        if name == "rev":
            p = ins[0]
            if p.kind == "axis" and p.dim in tuple(eqn.params["dimensions"]):
                return [mixed("rev")]  # volumes reordered
            return [p]

        if name in ("slice", "dynamic_slice"):
            p = ins[0]
            if p.kind != "axis":
                return [_elementwise_or_taint(ins, name)]
            in_shape = eqn.invars[0].aval.shape
            out_shape = eqn.outvars[0].aval.shape
            if out_shape[p.dim] != in_shape[p.dim]:
                return [mixed(name)]  # partial cut of the volume axis
            if name == "slice":
                strides = eqn.params.get("strides")
                if strides is not None and strides[p.dim] != 1:
                    return [mixed(name)]
            # dynamic start indices along other dims are scalars (NONE) or
            # per-volume offsets only via gather; taint if any index is
            # derived from cross-volume data
            for q in ins[1:]:
                if q.kind == "mixed":
                    return [q]
            return [axis(p.dim)]

        if name == "dynamic_update_slice":
            op, upd = ins[0], ins[1]
            for q in ins:
                if q.kind == "mixed":
                    return [q]
            if op.kind != "axis":
                if upd.kind == "axis":
                    return [mixed(name)]  # per-volume data into shared buf
                return [NONE]
            d = op.dim
            op_shape = eqn.invars[0].aval.shape
            upd_shape = eqn.invars[1].aval.shape
            full = len(upd_shape) == len(op_shape) and \
                upd_shape[d] == op_shape[d]
            if not full:
                return [mixed(name)]  # writes a sub-range of volumes
            if upd.kind == "axis" and upd.dim != d:
                return [mixed(name)]
            return [axis(d)]

        if name in ("concatenate", "pad"):
            if name == "concatenate":
                cat_dim = eqn.params["dimension"]
            else:
                cat_dim = None
                cfgs = eqn.params["padding_config"]
                for i, (lo, hi, interior) in enumerate(cfgs):
                    if lo or hi or interior:
                        cat_dim = i if cat_dim is None else cat_dim
                # padding multiple dims: only the volume dim matters below
                pad_dims = tuple(i for i, (lo, hi, inte) in enumerate(cfgs)
                                 if lo or hi or inte)
            out = NONE
            for i, p in enumerate(ins):
                if p.kind == "mixed":
                    return [p]
                if p.kind == "axis":
                    grows = (p.dim == cat_dim if name == "concatenate"
                             else p.dim in pad_dims)
                    if grows:
                        return [mixed(name)]  # volume axis resized
                    out = join(out, p)
            return [out]

        if name in ("reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
                    "reduce_and", "reduce_or", "reduce_xor",
                    "argmax", "argmin", "reduce_precision"):
            if name == "reduce_precision":
                return [ins[0]]
            axes = eqn.params.get("axes", ())
            return [_reduce_axes(ins[0], axes, name)]

        if name == "reduce":  # generic lax.reduce: computation + dims
            axes = eqn.params.get("dimensions", ())
            return [_reduce_axes(p, axes, name) for p in ins[:n_out]]

        if name.startswith("cum"):  # cumsum/cummax/cumlogsumexp/...
            p = ins[0]
            if p.kind == "axis" and eqn.params.get("axis") == p.dim:
                return [mixed(name)]  # prefix-scan across volumes
            return [p]

        if name == "sort":
            dim = eqn.params["dimension"]
            bad = any(p.kind == "mixed" for p in ins) or \
                any(p.kind == "axis" and p.dim == dim for p in ins)
            if bad:
                worst = _tainted(ins, name)
                return [worst if worst.kind == "mixed" else mixed(name)] \
                    * n_out
            # keys permute all operands within the sort dim; per-volume
            # rows never cross, and taint flows keys -> values
            out = NONE
            for p in ins:
                out = join(out, p)
            return [out] * n_out

        if name == "gather":
            return [self._gather(eqn, ins)]
        if name.startswith("scatter"):
            return [self._scatter(eqn, ins)]

        if name == "dot_general":
            return [self._dot_general(eqn, ins)]

        if name == "iota":
            return [NONE]

        if name in ("psum", "pmax", "pmin", "all_gather", "all_to_all",
                    "ppermute", "pbroadcast", "reduce_scatter",
                    "psum_invariant"):
            # cross-device collective: shards are different volumes, so the
            # result blends volumes even though shapes are elementwise
            return [mixed(name)] * n_out

        if name in ("axis_index", "iota_32x2_shape"):
            return [NONE] * n_out

        # generic elementwise: single output, every operand either scalar
        # or output-shaped; join provenances (same-dim axes agree)
        ew = _elementwise(eqn, ins)
        if ew is not None:
            return ew

        return self._unknown(eqn, ins)

    # -- structured primitives -------------------------------------------------

    def _while(self, eqn, ins):
        cn = eqn.params["cond_nconsts"]
        bn = eqn.params["body_nconsts"]
        body = eqn.params["body_jaxpr"]
        body_consts = ins[cn:cn + bn]
        carry = list(ins[cn + bn:])
        for _ in range(_MAX_FIXPOINT_ITERS):
            outs = self._jaxpr(body.jaxpr,
                               [NONE] * len(body.jaxpr.constvars),
                               body_consts + carry)
            new = [join(a, b) for a, b in zip(carry, outs)]
            if new == carry:
                break
            carry = new
        return carry

    def _scan(self, eqn, ins):
        nc = eqn.params["num_consts"]
        ncar = eqn.params["num_carry"]
        body = eqn.params["jaxpr"]
        consts = ins[:nc]
        carry = list(ins[nc:nc + ncar])
        xs = ins[nc + ncar:]
        # inside the body each xs leaf loses its leading scan dim
        xs_in = []
        for i, p in enumerate(xs):
            if p.kind == "axis":
                if p.dim == 0:
                    # scanning *over* the volume axis: each step sees one
                    # volume; anything accumulated into carry mixes them
                    xs_in.append(mixed("scan over volume axis"))
                else:
                    xs_in.append(axis(p.dim - 1))
            else:
                xs_in.append(p)
        for _ in range(_MAX_FIXPOINT_ITERS):
            outs = self._jaxpr(body.jaxpr,
                               [NONE] * len(body.jaxpr.constvars),
                               consts + carry + xs_in)
            new = [join(a, b) for a, b in zip(carry, outs[:ncar])]
            if new == carry:
                break
            carry = new
        outs = self._jaxpr(body.jaxpr, [NONE] * len(body.jaxpr.constvars),
                           consts + carry + xs_in)
        ys = []
        for p in outs[ncar:]:
            if p.kind == "axis":
                ys.append(axis(p.dim + 1))  # stacked under a new lead dim
            else:
                ys.append(p)
        return carry + ys

    # -- indexed primitives ----------------------------------------------------

    def _gather(self, eqn, ins):
        op, idx = ins[0], ins[1]
        if op.kind == "mixed":
            return op
        if idx.kind == "mixed":
            return idx
        dnums = eqn.params["dimension_numbers"]
        op_batch = tuple(getattr(dnums, "operand_batching_dims", ()) or ())
        idx_batch = tuple(getattr(dnums, "start_indices_batching_dims", ())
                          or ())
        slice_sizes = eqn.params["slice_sizes"]
        op_shape = eqn.invars[0].aval.shape

        if op.kind == "axis":
            d = op.dim
            if d in op_batch:
                # vmap's batched gather: volume v reads volume v's row only.
                # Output dim = where the matching indices batching dim lands.
                pos = op_batch.index(d)
                b = idx_batch[pos] if pos < len(idx_batch) else None
                out_d = (_gather_batch_pos(dnums, eqn.invars[1].aval.ndim, b)
                         if b is not None else None)
                if out_d is None:
                    return mixed("gather")
                return axis(out_d)
            if d in dnums.start_index_map or d in dnums.collapsed_slice_dims:
                return mixed("gather")  # indexed *across* the volume axis
            if slice_sizes[d] != op_shape[d]:
                return mixed("gather")  # partial window over volumes
            # full-extent pass-through slice dim -> its offset dim
            window = [i for i in range(len(op_shape))
                      if i not in dnums.collapsed_slice_dims
                      and i not in op_batch]
            out_d = dnums.offset_dims[window.index(d)]
            return axis(out_d)

        if idx.kind == "axis":
            b = idx.dim
            if b == eqn.invars[1].aval.ndim - 1:
                return mixed("gather")  # volume id used as a coordinate
            out_d = _gather_batch_pos(dnums, eqn.invars[1].aval.ndim, b)
            if out_d is None:
                return mixed("gather")
            return axis(out_d)

        return NONE

    def _scatter(self, eqn, ins):
        name = eqn.primitive.name
        op, idx, upd = ins[0], ins[1], ins[2]
        for p in (op, idx, upd):
            if p.kind == "mixed":
                return p
        dnums = eqn.params["dimension_numbers"]
        op_batch = tuple(getattr(dnums, "operand_batching_dims", ()) or ())
        idx_batch = tuple(getattr(dnums, "scatter_indices_batching_dims", ())
                          or ())
        op_shape = eqn.invars[0].aval.shape
        idx_rank = eqn.invars[1].aval.ndim
        upd_shape = eqn.invars[2].aval.shape

        if op_batch:
            # vmap's batched scatter: volume v writes only volume v's rows,
            # provided every per-volume input rides its own batch dim
            d = op_batch[0]
            ok = op.kind != "axis" or op.dim == d
            if idx.kind == "axis":
                ok = ok and idx.dim in idx_batch
            if upd.kind == "axis":
                upd_scatter_dims = [i for i in range(len(upd_shape))
                                    if i not in dnums.update_window_dims]
                b = idx_batch[0] if idx_batch else None
                ok = ok and b is not None and b < idx_rank - 1 and \
                    upd.dim == upd_scatter_dims[b]
            if not ok:
                return mixed(name)
            if "axis" in (op.kind, idx.kind, upd.kind):
                return axis(d)
            return NONE

        if op.kind == "axis":
            d = op.dim
            if d in dnums.scatter_dims_to_operand_dims or \
                    d in dnums.inserted_window_dims:
                return mixed(name)  # indices choose which volume to write
            # d is a window dim: updates must span the whole volume axis
            window = [i for i in range(len(op_shape))
                      if i not in dnums.inserted_window_dims]
            upd_d = dnums.update_window_dims[window.index(d)]
            if upd_shape[upd_d] != op_shape[d]:
                return mixed(name)
            if upd.kind == "axis" and upd.dim != upd_d:
                return mixed(name)
            return axis(d)

        if upd.kind == "axis":
            # per-volume updates written into a uniform buffer: safe only
            # when they ride a full-extent window dim (volume rows map 1:1
            # onto an operand dim, no index-dependent placement)
            u = upd.dim
            wdims = list(dnums.update_window_dims)
            if u not in wdims or idx.kind == "axis":
                return mixed(name)
            window = [i for i in range(len(op_shape))
                      if i not in dnums.inserted_window_dims]
            op_d = window[wdims.index(u)]
            if upd_shape[u] != op_shape[op_d]:
                return mixed(name)
            return axis(op_d)
        if idx.kind == "axis":
            return mixed(name)  # per-volume placement into shared buf
        return NONE

    def _dot_general(self, eqn, ins):
        a, b = ins[0], ins[1]
        if a.kind == "none" and b.kind == "none":
            return NONE
        for p in (a, b):
            if p.kind == "mixed":
                return p
        dnums = eqn.params["dimension_numbers"]
        (lc, rc), (lb, rb) = dnums
        out = NONE
        for p, contract, batch in ((a, lc, lb), (b, rc, rb)):
            if p.kind != "axis":
                continue
            if p.dim in contract:
                return mixed("dot_general")  # contracted over volumes
            if p.dim in batch:
                out = join(out, axis(tuple(batch).index(p.dim)))
            else:
                return mixed("dot_general")  # broadcast against volumes
        return out

    # -- fallbacks -------------------------------------------------------------

    def _unknown(self, eqn, ins):
        """No rule: sound over precise. Per-volume inputs come out Mixed."""
        worst = _tainted(ins, eqn.primitive.name)
        # still descend so nested per-volume flows inside opaque bodies
        # (pallas_call) don't silently vanish from a future rule's view
        for sub, _ in subjaxprs(eqn):
            self._jaxpr(sub, [NONE] * len(sub.constvars),
                        [worst] * len(sub.invars))
        return [worst] * len(eqn.outvars)


def _elementwise(eqn, ins):
    """Join rule for shape-preserving elementwise primitives: every operand
    is rank-0, or output-ranked with each dim equal to the output's or 1
    (lax's implicit size-1 broadcasting). Position-preserving, so an
    operand's volume axis stays at its own dim. Returns None if the eqn
    does not fit that shape discipline."""
    if len(eqn.outvars) != 1:
        return None
    out_shape = getattr(eqn.outvars[0].aval, "shape", None)
    if out_shape is None:
        return None
    out = NONE
    for atom, p in zip(eqn.invars, ins):
        shape = getattr(atom.aval, "shape", ())
        if shape == ():
            out = join(out, p)      # rank-0 carries no axis (NONE or Mixed)
            continue
        if len(shape) != len(out_shape):
            return None
        if any(s != o and s != 1 for s, o in zip(shape, out_shape)):
            return None
        if p.kind == "axis" and shape[p.dim] == 1:
            return None             # a size-1 dim cannot be the volume axis
        out = join(out, p)
    return [out]


def _elementwise_or_taint(ins, name):
    out = NONE
    for p in ins:
        out = join(out, p)
    return out


def volume_seeds(closed_jaxpr) -> list:
    """Seed provenances for a fleet trace: every non-scalar input is
    V-leading by construction (batched state leaves, (V,)/(V,T) trace and
    policy arrays), scalars are uniform."""
    return [axis(0) if len(v.aval.shape) >= 1 else NONE
            for v in closed_jaxpr.jaxpr.invars]
