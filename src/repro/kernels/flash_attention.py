"""Flash attention (causal/windowed, GQA) as a Pallas TPU kernel.

Motivation (EXPERIMENTS §Perf D4): the pure-JAX chunked-attention scan
carries its (m, l, acc) online-softmax state through HBM on every KV chunk —
at deepseek train_4k that is ~34 GB of accumulator traffic per layer. Here
the state lives in VMEM scratch across the KV grid dimension, so HBM sees
only Q/K/V reads and one O write (the flash-attention property).

Grid: (B * Hq, S/bq, S/bk) with the KV dimension innermost ("arbitrary"
semantics — sequential); scratch (m, l, acc) persists across KV steps, is
initialized at ik == 0 and flushed to the output block at the last step.
Causal + sliding-window masking is applied per (bq, bk) tile; fully-masked
tiles skip the matmul via pl.when.

Validated against ref.flash_attention_ref over shape/GQA/window sweeps in
interpret mode (tests/test_kernels.py); TPU is the target.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, causal: bool, window: int | None,
                  scale: float, n_kv_blocks: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * bq
    k_start = ik * bk
    # tile-level reachability: any (causal/window)-visible pair in this tile?
    tile_visible = True
    if causal:
        tile_visible = q_start + bq - 1 >= k_start
    if window is not None:
        tile_visible = jnp.logical_and(
            tile_visible, q_start <= k_start + bk - 1 + window - 1
        ) if causal else tile_visible

    @pl.when(tile_visible if isinstance(tile_visible, jax.Array) else
             jnp.bool_(tile_visible))
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, dh)
        k = k_ref[0].astype(jnp.float32)                  # (bk, dh)
        v = v_ref[0].astype(jnp.float32)                  # (bk, dhv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                                 # (bq, bk)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ik == n_kv_blocks - 1)
    def _flush():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret",
                     "softmax_scale"),
)
def flash_attention(
    q: jax.Array,   # (B, S, Hq, dh)
    k: jax.Array,   # (B, S, Hkv, dh)
    v: jax.Array,   # (B, S, Hkv, dhv)
    causal: bool = True,
    window: int | None = None,
    softmax_scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, S, Hq, dh = q.shape
    Hkv, dhv = k.shape[2], v.shape[-1]
    G = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else dh**-0.5
    bq = min(block_q, S)
    bk = min(block_k, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    nq, nk = S // bq, S // bk

    # layout: fold heads into the leading grid dim; kv heads shared by G
    qh = q.transpose(0, 2, 1, 3).reshape(B * Hq, S, dh)
    kh = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, dh)
    vh = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, dhv)

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, causal=causal, window=window,
        scale=scale, n_kv_blocks=nk,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda h, iq, ik: (h, iq, 0)),
            pl.BlockSpec((1, bk, dh), lambda h, iq, ik, G=G: (h // G, ik, 0)),
            pl.BlockSpec((1, bk, dhv), lambda h, iq, ik, G=G: (h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dhv), lambda h, iq, ik: (h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, S, dhv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, dhv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qh, kh, vh)
    return out.reshape(B, Hq, S, dhv).transpose(0, 2, 1, 3)
