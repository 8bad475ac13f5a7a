"""Pallas TPU kernel: flash attention (online-softmax, tiled), with causal
masking, optional sliding window (Mixtral/RecurrentGemma) and GQA head
mapping — the LM stack's prefill hot spot (beyond-paper kernel, DESIGN §2).

Grid (B, Hq, Tq/bq, Tk/bk) with the key axis innermost-sequential; running
(max, denom, acc) live in VMEM scratch across key steps, so scores never
materialize in HBM: O(T²) compute, O(T) memory. The GQA mapping happens in
the K/V index_map (query head h reads kv head h // group) — no repeat of
K/V in memory.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
            bq: int, bk: int, tq: int, tk: int, causal: bool, window):
    qt = pl.program_id(2)
    kt = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kt == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0, 0].astype(jnp.float32)       # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)       # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)
    d = q.shape[-1]

    s = (q @ k.T) * (1.0 / jnp.sqrt(jnp.float32(d)))   # (bq, bk)
    qpos = qt * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + (tk - tq)
    kpos = kt * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, _NEG)

    m_prev = m_s[...]                          # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_s[...] = l_s[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_s[...] = acc_s[...] * alpha + p @ v
    m_s[...] = m_new

    @pl.when(kt == nk - 1)
    def _emit():
        denom = jnp.maximum(l_s[...], 1e-30)
        o_ref[0, 0] = (acc_s[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "interpret",
                                    "block_q", "block_k"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int | None = None,
                           interpret: bool,
                           block_q: int = 128, block_k: int = 128) -> jax.Array:
    """q (B, Hq, Tq, D); k, v (B, Hkv, Tk, D) → (B, Hq, Tq, D)."""
    b, hq, tq, d = q.shape
    _, hkv, tk, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv

    bq = min(block_q, tq)
    while tq % bq:
        bq -= 1
    bk = min(block_k, tk)
    while tk % bk:
        bk -= 1

    return pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, tq=tq, tk=tk,
                          causal=causal, window=window),
        grid=(b, hq, tq // bq, tk // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, qt, kt: (b_, h, qt, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, qt, kt: (b_, h // group, kt, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, qt, kt: (b_, h // group, kt, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h, qt, kt: (b_, h, qt, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
