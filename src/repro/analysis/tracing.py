"""Trace schemes, kernels, and the tick engine to jaxprs for the lints.

Every trace runs ``jax.make_jaxpr`` with *abstract* inputs sized from a
:class:`~repro.core.jaxsim.JaxSimConfig` — nothing executes on a device.
The resulting :class:`TraceRecord` pairs the closed jaxpr with the pytree
paths of its flattened inputs/outputs (so the lints can talk about state
*keys*, not flat argument slots) and with seed intervals for the interval
engine (``lba`` really is in ``[0, n_lbas)``; ``t`` is a non-negative
clock; booleans are 0/1).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.tree_util import DictKey, SequenceKey, tree_flatten_with_path

from repro.core import jaxsim
from repro.core.placement.jax_schemes import NOBIT

from .intervals import INF, UNKNOWN


def probe_config(n_lbas: int = 256, segment_size: int = 16,
                 **kw) -> "jaxsim.JaxSimConfig":
    """The config the analyzer sizes its abstract inputs from. Small enough
    to trace fast; the contracts under check are size-independent."""
    return jaxsim.JaxSimConfig(n_lbas=n_lbas, segment_size=segment_size, **kw)


@dataclasses.dataclass
class TraceRecord:
    """One traced entry point plus the metadata the lints need."""

    label: str                       # e.g. "dac.user_class"
    closed_jaxpr: object
    in_paths: list                   # pytree paths aligned with invars
    out_paths: list                  # pytree paths aligned with outvars
    seeds: list                      # input intervals aligned with invars
    state_in: dict                   # state key -> invar slot
    state_out: dict                  # state key -> outvar slot
    class_out: int | None = None     # outvar slot of the class output
    scheme: str | None = None

    @property
    def jaxpr(self):
        return self.closed_jaxpr.jaxpr


def _path_head_dict_key(path, arg_idx):
    """State key when this leaf lives in the dict at argument ``arg_idx``
    (or at the pytree root for ``arg_idx is None``)."""
    if arg_idx is None:
        if len(path) == 1 and isinstance(path[0], DictKey):
            return path[0].key
        return None
    if (len(path) >= 2 and path[0] == SequenceKey(arg_idx)
            and isinstance(path[1], DictKey)):
        return path[1].key
    return None


def trace(label, fn, args, *, state_arg=None, state_out=None,
          class_out=None, arg_seeds=None, state_seeds=None, scheme=None):
    """Trace ``fn(*args)`` (args: pytrees of ``jax.ShapeDtypeStruct``).

    ``state_arg`` / ``state_out``: which input argument / output tuple slot
    holds the state dict ("root" for a bare-dict output). ``class_out``:
    output tuple slot holding the class id(s). ``arg_seeds``: interval per
    scalar argument index; ``state_seeds``: interval per state key.
    """
    closed_jaxpr, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args)
    in_leaves, _ = tree_flatten_with_path(tuple(args))
    out_leaves, _ = tree_flatten_with_path(out_shape)
    assert len(in_leaves) == len(closed_jaxpr.jaxpr.invars), label
    assert len(out_leaves) == len(closed_jaxpr.jaxpr.outvars), label

    arg_seeds = arg_seeds or {}
    state_seeds = state_seeds or {}
    seeds, state_in = [], {}
    for i, (path, leaf) in enumerate(in_leaves):
        key = _path_head_dict_key(path, state_arg)
        if key is not None:
            state_in[key] = i
        if key is not None and key in state_seeds:
            seeds.append(state_seeds[key])
        elif (key is None and len(path) == 1
                and isinstance(path[0], SequenceKey)
                and path[0].idx in arg_seeds):
            seeds.append(arg_seeds[path[0].idx])
        elif np.dtype(leaf.dtype) == np.bool_:
            seeds.append((0.0, 1.0))
        else:
            seeds.append(UNKNOWN)

    state_out_map, class_slot = {}, None
    for j, (path, _) in enumerate(out_leaves):
        key = _path_head_dict_key(
            path, None if state_out == "root" else state_out)
        if key is not None:
            state_out_map[key] = j
        if class_out is not None and path == (SequenceKey(class_out),):
            class_slot = j

    return TraceRecord(label=label, closed_jaxpr=closed_jaxpr,
                       in_paths=[p for p, _ in in_leaves],
                       out_paths=[p for p, _ in out_leaves],
                       seeds=seeds, state_in=state_in,
                       state_out=state_out_map, class_out=class_slot,
                       scheme=scheme)


# -- entry-point harnesses -----------------------------------------------------

_SHARED_SEEDS = {"t": (0.0, INF), "ell": (0.0, INF),
                 "loc_seg": (-1.0, INF), "loc_off": (0.0, INF)}


def full_state_spec(cfg, impl=None):
    """The engine's carried state spec, extended with ``impl``'s slice when
    the implementation is not registered (violation fixtures)."""
    spec = dict(jaxsim.state_spec(cfg))
    if impl is not None:
        extra = jax.eval_shape(lambda: impl.init_state(cfg))
        spec.update({k: v for k, v in extra.items() if k not in spec})
    return spec


def scheme_traces(cfg, name, impl):
    """(user_class, gc_classes) traces for one JaxPlacement triple."""
    spec = full_state_spec(cfg, impl)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    s = cfg.segment_size
    vec_i = jax.ShapeDtypeStruct((s,), jnp.int32)
    vec_b = jax.ShapeDtypeStruct((s,), jnp.bool_)

    user = trace(
        f"{name}.user_class",
        lambda st, lba, v, nxt: impl.user_class(cfg, st, lba, v, nxt),
        (spec, scalar, scalar, scalar),
        state_arg=0, state_out=1, class_out=0, scheme=name,
        arg_seeds={1: (0.0, cfg.n_lbas - 1), 2: (0.0, INF),
                   3: (0.0, float(NOBIT))},
        state_seeds=_SHARED_SEEDS)
    gc = trace(
        f"{name}.gc_classes",
        lambda st, vc, lv, ut, va, g: impl.gc_classes(cfg, st, vc, lv,
                                                      ut, va, g),
        (spec, scalar, vec_i, vec_i, vec_b, vec_i),
        state_arg=0, state_out=1, class_out=0, scheme=name,
        arg_seeds={1: (0.0, cfg.n_class_slots - 1),
                   2: (0.0, cfg.n_lbas - 1), 3: (0.0, INF), 5: (0.0, INF)},
        state_seeds=_SHARED_SEEDS)
    return [user, gc]


def engine_trace(cfg):
    """One full user step (write + GC trigger loop) under the registry-wide
    dispatch switch — the jaxpr ``lax.scan`` carries, whose in/out state
    specs the drift lint compares."""
    spec = full_state_spec(cfg)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    return trace(
        "jaxsim._user_step",
        lambda st, lba, nxt: jaxsim._user_step(cfg, st, lba, nxt),
        (spec, scalar, scalar),
        state_arg=0, state_out="root",
        arg_seeds={1: (0.0, cfg.n_lbas - 1), 2: (0.0, float(NOBIT))},
        state_seeds=_SHARED_SEEDS)


def batched_state_spec(cfg, n_volumes, impl=None):
    """The fleet scan carry: every engine state leaf with a leading volume
    axis (what ``vmap(init_state)`` produces)."""
    return {k: jax.ShapeDtypeStruct((n_volumes,) + v.shape, v.dtype)
            for k, v in full_state_spec(cfg, impl).items()}


def _policy_spec(cfg, n_volumes):
    return {k: jax.ShapeDtypeStruct((n_volumes,) + v.shape, v.dtype)
            for k, v in jax.eval_shape(
                lambda: jaxsim.default_policy(cfg)).items()}


def fleet_traces(cfg, n_volumes=4, horizon=6):
    """The vmapped fleet engine's entry points: one synchronized tick
    (``fleet_step``), the GC tick loop alone (``fleet_gc_tick``), and the
    whole replay (``fleet_body`` — vmapped init + scan over time). The
    SA5xx volume-isolation lints run over these."""
    V, T = n_volumes, horizon
    spec = batched_state_spec(cfg, V)
    vec = jax.ShapeDtypeStruct((V,), jnp.int32)
    vecb = jax.ShapeDtypeStruct((V,), jnp.bool_)
    mat = jax.ShapeDtypeStruct((V, T), jnp.int32)
    step = trace(
        "fleet.step",
        lambda st, lbas, nxts: jaxsim.fleet_step(cfg, True, st, lbas, nxts),
        (spec, vec, vec), state_arg=0, state_out="root",
        state_seeds=_SHARED_SEEDS)
    tick = trace(
        "fleet.gc_tick",
        lambda st, act: jaxsim.fleet_gc_tick(cfg, st, act),
        (spec, vecb), state_arg=0, state_out="root",
        state_seeds=_SHARED_SEEDS)
    body = trace(
        "fleet.body",
        lambda tr, nx, pol: jaxsim.fleet_body(cfg, True, tr, nx, pol),
        (mat, mat, _policy_spec(cfg, V)), state_out="root")
    return [step, tick, body]


def fleet_shard_trace(cfg, n_volumes=4, horizon=6, mesh=None):
    """The exact ``shard_map(fleet_body)`` program `_sharded_runner` jits,
    traced over whatever mesh is available (a 1-device mesh suffices: a
    collective over the ``"fleet"`` axis is visible in the jaxpr no matter
    the device count). The SA502 collective lint runs over this."""
    from jax.sharding import Mesh

    from repro.core import fleetshard
    if mesh is None:
        mesh = fleetshard.fleet_mesh(min_devices=2) or Mesh(
            np.asarray(jax.devices()[:1]), ("fleet",))
    V = -(-n_volumes // mesh.size) * mesh.size   # round up to a shard multiple
    mat = jax.ShapeDtypeStruct((V, horizon), jnp.int32)
    body = fleetshard.shard_mapped_body(cfg, True, mesh)
    return trace("fleet.shard_body", body, (mat, mat, _policy_spec(cfg, V)),
                 state_out="root")


def fleet_fixture_trace(cfg, fx, n_volumes=4):
    """Trace one fleet violation fixture: a batched-state step function,
    shard_map-wrapped for ``kind == "fleet_shard"`` fixtures (collectives
    only bind inside a mesh context)."""
    spec = batched_state_spec(cfg, n_volumes)

    def fn(st):
        return fx.impl(cfg, st)
    if fx.kind == "fleet_shard":
        from jax.sharding import Mesh, PartitionSpec
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("fleet",))
        fn = jax.shard_map(fn, mesh=mesh, in_specs=(PartitionSpec("fleet"),),
                           out_specs=PartitionSpec("fleet"), check_vma=False)
    return trace(f"fleet.{fx.name}", fn, (spec,), state_arg=0,
                 state_out="root")


def kernel_traces():
    """Traces of every kernel entry point declared for analysis (the Pallas
    classify / segment-select kernels and their jnp oracles)."""
    from repro.kernels import classify, ref, segsel
    recs = []
    for mod in (classify, segsel, ref):
        for label, (fn, args) in mod.analysis_entries().items():
            recs.append(trace(label, fn, args))
    return recs
