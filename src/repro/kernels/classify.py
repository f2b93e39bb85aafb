"""Pallas TPU kernel: placement-class assignment, generated from the registry.

Fuses the per-block placement decision over a *batch* of written blocks —
the form the decision takes in the GC hot path and the serving integration,
where a compaction tick classifies thousands of pages at once.

The kernel body is built from the placement registry
(`core/placement/registry.py`): every registered JAX scheme that declares an
``elementwise`` classifier ``fn(v, g, from_c1, is_gc, ell) -> cls`` (nosep,
sepgc, sepbit and the Exp#4 ablations uw/gw) is compiled into one select
chain keyed on the *runtime* scheme-id scalar — heterogeneous fleets vmap
this kernel with a different scheme per volume, so the choice cannot be
baked into the compiled kernel. Registering a new elementwise scheme lands
it here automatically; stateful schemes (fk/dac/ml/sfs and the
shared-classifier ports eti/mq/sfr/fadac/warcip) classify via their jnp
branch in `jaxsim._gc_class_dispatch` and never consult this kernel.

Inputs: v (predecessor lifespan), g (age), from_c1 / is_gc flags, and the
scalar ell; elementwise over (8,128)-tiled int32 blocks on the VPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.placement.jax_schemes import elementwise_chain
from repro.core.placement.registry import jax_scheme_id
from repro.kernels import interpret_mode

LANE = 128
TILE_ROWS = 8


def _make_classify_kernel(scheme_ids: tuple[int, ...] | None):
    def _classify_kernel(ell_ref, scheme_ref, v_ref, g_ref, from_c1_ref,
                         is_gc_ref, out_ref):
        out_ref[...] = elementwise_chain(
            scheme_ref[0, 0],
            v_ref[...].astype(jnp.float32), g_ref[...].astype(jnp.float32),
            from_c1_ref[...], is_gc_ref[...], ell_ref[0, 0],
            scheme_ids=scheme_ids)
    return _classify_kernel


@functools.partial(jax.jit, static_argnames=("scheme_ids", "interpret"))
def classify(v: jax.Array, g: jax.Array, from_c1: jax.Array, is_gc: jax.Array,
             ell: jax.Array, *, scheme_id: jax.Array | None = None,
             scheme_ids: tuple[int, ...] | None = None,
             interpret: bool | None = None) -> jax.Array:
    """Placement class ids for a batch of writes. 1-D equal-length inputs.
    ``scheme_id`` (traced int32 scalar) selects the scheme per call/volume;
    omitted = SepBIT (the historical behavior). Only elementwise-registered
    scheme ids produce meaningful classes; others yield class 0.

    ``scheme_ids`` (static tuple of global dense ids) prunes the kernel's
    select chain to those schemes — the grouped-dispatch path compiles one
    kernel per scheme group instead of chaining the whole zoo. Ids inside
    the tuple classify identically to the full chain; a runtime
    ``scheme_id`` outside the tuple yields class 0. ``interpret`` follows
    the platform (see `repro.kernels.interpret_mode`)."""
    (B,) = v.shape
    tile = TILE_ROWS * LANE
    Bp = ((B + tile - 1) // tile) * tile
    pad = Bp - B
    if scheme_id is None:
        scheme_id = jnp.int32(jax_scheme_id("sepbit"))

    def prep(x):
        return jnp.pad(x.astype(jnp.int32), (0, pad)).reshape(Bp // LANE, LANE)

    v2, g2, c12, gc2 = map(prep, (v, g, from_c1, is_gc))
    spec = pl.BlockSpec((TILE_ROWS, LANE), lambda i: (i, 0))
    scalar = pl.BlockSpec((1, 1), lambda i: (0, 0))
    out = pl.pallas_call(
        _make_classify_kernel(scheme_ids),
        grid=(Bp // tile,),
        in_specs=[scalar, scalar, spec, spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((Bp // LANE, LANE), jnp.int32),
        interpret=interpret_mode(interpret),
    )(ell.reshape(1, 1).astype(jnp.float32),
      jnp.asarray(scheme_id, jnp.int32).reshape(1, 1), v2, g2, c12, gc2)
    return out.reshape(-1)[:B]


def analysis_entries(batch: int = 2048):
    """Traceable entry points for the static analyzer (`repro.analysis`):
    label -> (fn, abstract args). The analyzer runs its overflow/purity
    lints over the traced kernel body, Pallas inner jaxpr included."""
    vec = jax.ShapeDtypeStruct((batch,), jnp.int32)
    scalar_f = jax.ShapeDtypeStruct((), jnp.float32)
    scalar_i = jax.ShapeDtypeStruct((), jnp.int32)
    return {
        "kernels.classify": (
            lambda v, g, c1, gc, ell, sid: classify(v, g, c1, gc, ell,
                                                    scheme_id=sid),
            (vec, vec, vec, vec, scalar_f, scalar_i)),
    }
