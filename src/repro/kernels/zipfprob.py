"""Pallas TPU kernel: Zipf BIT-inference probabilities (paper §3.2-§3.3).

Computes the three reduction sums behind Figures 8 and 10 over the pmf
p (n ≈ 2.6M for the paper's 10 GiB working set):

  num_u  = Σ p · (1-(1-p)^u0) · (1-(1-p)^v0)     } Fig 8: Pr(u<=u0 | v<=v0)
  den_v  = Σ p · (1-(1-p)^v0)                    }
  den_g  = Σ p · (1-p)^g0                        } Fig 10: Pr(u<=g0+r0 | u>=g0)
  num_g  = Σ p · ((1-p)^g0 - (1-p)^(g0+r0))      }

(1-p)^e is exp(e·log1p(-p)) — transcendental-heavy, compute-bound, a clean
VPU tile reduction. Each grid step folds its (TILE_ROWS, 128) tile into four
(8, 128) partial-sum tiles carried in the output block (constant index map);
the last 8×128-way sum runs in jnp after the call, so the kernel never
stores a scalar to VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

LANE = 128
SUBLANES = 8
TILE_ROWS = 64  # bigger tiles: reduction is compute-bound


def _zipf_kernel(e_ref, p_ref, out_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    u0, v0, g0, r0 = e_ref[0, 0], e_ref[0, 1], e_ref[0, 2], e_ref[0, 3]
    p = p_ref[...]
    lg = jnp.log1p(-p)          # log(1-p); p in [0,1)
    pow_u0 = jnp.exp(u0 * lg)
    pow_v0 = jnp.exp(v0 * lg)
    pow_g0 = jnp.exp(g0 * lg)
    pow_gr = jnp.exp((g0 + r0) * lg)

    def fold(x):                # (TILE_ROWS, LANE) -> (SUBLANES, LANE)
        return jnp.sum(x.reshape(TILE_ROWS // SUBLANES, SUBLANES, LANE), axis=0)

    out_ref[0] += fold(p * (1.0 - pow_u0) * (1.0 - pow_v0))    # num_u
    out_ref[1] += fold(p * (1.0 - pow_v0))                     # den_v
    out_ref[2] += fold(p * pow_g0)                             # den_g
    out_ref[3] += fold(p * (pow_g0 - pow_gr))                  # num_g


@functools.partial(jax.jit, static_argnames=("interpret",))
def zipf_bit_sums(probs: jax.Array, u0: float, v0: float, g0: float, r0: float,
                  *, interpret: bool | None = None) -> jax.Array:
    """Returns [num_u, den_v, den_g, num_g]; padding (p=0) contributes 0."""
    (n,) = probs.shape
    tile = TILE_ROWS * LANE
    np_ = ((n + tile - 1) // tile) * tile
    p2 = jnp.pad(probs.astype(jnp.float32), (0, np_ - n)).reshape(np_ // LANE, LANE)
    exps = jnp.array([[u0, v0, g0, r0]], dtype=jnp.float32)
    acc = pl.BlockSpec((4, SUBLANES, LANE), lambda i: (0, 0, 0))
    out = pl.pallas_call(
        _zipf_kernel,
        grid=(np_ // tile,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((TILE_ROWS, LANE), lambda i: (i, 0)),
        ],
        out_specs=acc,
        out_shape=jax.ShapeDtypeStruct((4, SUBLANES, LANE), jnp.float32),
        interpret=interpret_mode(interpret),
    )(exps, p2)
    return jnp.sum(out, axis=(1, 2))


def pr_user_bit_kernel(probs, u0, v0, *, interpret: bool | None = None) -> jax.Array:
    s = zipf_bit_sums(probs, u0, v0, 0.0, 0.0, interpret=interpret)
    return s[0] / jnp.maximum(s[1], 1e-30)


def pr_gc_bit_kernel(probs, g0, r0, *, interpret: bool | None = None) -> jax.Array:
    s = zipf_bit_sums(probs, 0.0, 0.0, g0, r0, interpret=interpret)
    return s[3] / jnp.maximum(s[2], 1e-30)
