"""Pallas TPU kernel: flash-decode attention (single-token GQA decode).

The serving-side hot spot once the KV store is paged (serving/logkv): one new
query token attends over a long KV history. The kernel streams K/V tiles
HBM→VMEM (T rows at a time), computes (G, T) scores on the MXU for the G
query heads sharing a KV head, and maintains the online-softmax running
(max, denom, accumulator) in VMEM scratch across the KV-tile grid axis.

Grid: (batch, kv_heads, S/T); the KV axis is innermost so the scratch carries
per (batch, kv_head). Lengths mask ragged KV (continuous batching).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, kv_tile, scale):
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (G, D)
    k = k_ref[0, :, 0].astype(jnp.float32)            # (T, D)
    v = v_ref[0, :, 0].astype(jnp.float32)            # (T, D)

    scores = jax.lax.dot_general(                      # (G, T)
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    kv_len = len_ref[0, 0]
    pos = s_idx * kv_tile + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(pos < kv_len, scores, NEG_INF)

    m_prev = m_ref[:, :1]                              # (G, 1)
    m_cur = jnp.max(scores, axis=1, keepdims=True)     # (G, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)                    # rescale old state
    p = jnp.exp(scores - m_new)                        # (G, T)

    l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
    acc_new = alpha * acc_ref[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    m_ref[:, :1] = m_new
    l_ref[:, :1] = l_new
    acc_ref[...] = acc_new

    @pl.when(s_idx == pl.num_programs(2) - 1)
    def _fini():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kv_tile", "interpret"))
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array, kv_len: jax.Array,
                 *, kv_tile: int = 256,
                 interpret: bool | None = None) -> jax.Array:
    """Single-token GQA decode attention.

    q: (B, Hq, D); k, v: (B, S, Hkv, D); kv_len: (B,) valid KV entries.
    Hq % Hkv == 0; G = Hq // Hkv is padded to 8 sublanes internally.
    Returns (B, Hq, D).
    """
    B, Hq, D = q.shape
    _, S, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    Gp = max(8, ((G + 7) // 8) * 8)
    Sp = ((S + kv_tile - 1) // kv_tile) * kv_tile
    scale = 1.0 / (D ** 0.5)

    # (B, Hkv, G, D) with G padded to sublane multiple
    qg = q.reshape(B, Hkv, G, D)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Gp - G), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    lens = jnp.broadcast_to(kv_len.astype(jnp.int32)[:, None], (B, 1))

    grid = (B, Hkv, Sp // kv_tile)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, kv_tile=kv_tile, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, h, s: (b, 0)),
            # q viewed as (B*Hkv, Gp, D): one (Gp, D) row-block per (b, h)
            pl.BlockSpec((1, Gp, D), lambda b, h, s, H=Hkv: (b * H + h, 0, 0)),
            pl.BlockSpec((1, kv_tile, 1, D), lambda b, h, s: (b, s, h, 0)),
            pl.BlockSpec((1, kv_tile, 1, D), lambda b, h, s: (b, s, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, Gp, D), lambda b, h, s, H=Hkv: (b * H + h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, Gp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((Gp, 128), jnp.float32),
            pltpu.VMEM((Gp, 128), jnp.float32),
            pltpu.VMEM((Gp, D), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(lens, qg.reshape(B * Hkv, Gp, D), kp, vp)
    out = out.reshape(B, Hkv, Gp, D)[:, :, :G]
    return out.reshape(B, Hq, D)
