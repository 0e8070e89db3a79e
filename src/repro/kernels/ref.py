"""Pure-jnp oracles for every Pallas kernel. The kernels must match these
(assert_allclose over shape/dtype sweeps in tests/test_kernels.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def distance_matrix_ref(x: jax.Array, y: jax.Array, metric: str = "l2") -> jax.Array:
    """(q, d) x (n, d) -> (q, n) distances; fp32 accumulation."""
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    if metric == "cos":
        x = x * jax.lax.rsqrt(jnp.maximum(jnp.sum(x * x, -1, keepdims=True), 1e-12))
        y = y * jax.lax.rsqrt(jnp.maximum(jnp.sum(y * y, -1, keepdims=True), 1e-12))
        return 1.0 - x @ y.T
    if metric == "ip":
        return -(x @ y.T)
    xx = jnp.sum(x * x, axis=-1)[:, None]
    yy = jnp.sum(y * y, axis=-1)[None, :]
    return jnp.maximum(xx - 2.0 * (x @ y.T) + yy, 0.0)


def _distances_from_rows(
    queries: jax.Array, ids: jax.Array, rows: jax.Array, metric: str
) -> jax.Array:
    """queries (Q, d) vs gathered rows (Q, R, d) -> (Q, R); ids < 0 -> +inf."""
    q = queries[:, None, :].astype(jnp.float32)
    rows = rows.astype(jnp.float32)
    if metric == "ip":
        d = -jnp.sum(rows * q, axis=-1)
    elif metric == "cos":
        qn = q * jax.lax.rsqrt(jnp.maximum(jnp.sum(q * q, -1, keepdims=True), 1e-12))
        rn = rows * jax.lax.rsqrt(
            jnp.maximum(jnp.sum(rows * rows, -1, keepdims=True), 1e-12)
        )
        d = 1.0 - jnp.sum(rn * qn, axis=-1)
    else:
        diff = rows - q
        d = jnp.sum(diff * diff, axis=-1)
    return jnp.where(ids >= 0, d, jnp.inf)


def gather_distance_ref(
    queries: jax.Array, ids: jax.Array, base: jax.Array, metric: str = "l2"
) -> jax.Array:
    """queries (Q, d), ids (Q, R) into base (n, d) -> (Q, R) distances.

    Padding ids (< 0) produce +inf. This is the beam-search inner loop.
    """
    rows = base[jnp.maximum(ids, 0)]  # (Q, R, d)
    return _distances_from_rows(queries, ids, rows, metric)


def gather_distance_onehot_ref(
    queries: jax.Array, ids: jax.Array, base: jax.Array, metric: str = "l2"
) -> jax.Array:
    """Small-n fallback: the gather is a one-hot matmul (MXU-friendly on TPU,
    a dense XLA contraction on CPU), so the whole inner loop stays on the
    matrix unit for bases that fit a (Q, R, n) one-hot. Bit-identical to
    ``gather_distance_ref``: the 0/1 contraction reproduces rows exactly.
    """
    oh = jax.nn.one_hot(jnp.maximum(ids, 0), base.shape[0], dtype=jnp.float32)
    # HIGHEST: a 0/1 x fp32 contraction is exact only without bf16 truncation
    rows = jnp.einsum("qrn,nd->qrd", oh, base.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    return _distances_from_rows(queries, ids, rows, metric)


@jax.named_scope("visited_test")
def visited_mask_ref(ids: jax.Array, visited: jax.Array) -> jax.Array:
    """ids (Q, R) against a bit-packed (Q, ceil(n/32)) uint32 visited bitmap
    -> ids with padding (< 0) and already-visited entries set to -1. Every
    masked scorer runs this test under the ``visited_test`` scope, inside
    whatever scope its caller runs in."""
    Q, W = visited.shape
    safe = jnp.maximum(ids, 0)
    q = jnp.broadcast_to(jnp.arange(Q)[:, None], ids.shape)
    words = visited[q, jnp.minimum(safe >> 5, W - 1)]
    seen = (words >> (safe & 31).astype(jnp.uint32)) & 1 > 0
    return jnp.where((ids >= 0) & ~seen, ids, -1)


def gather_distance_masked_ref(
    queries: jax.Array,
    ids: jax.Array,
    base: jax.Array,
    visited: jax.Array,
    metric: str = "l2",
) -> tuple[jax.Array, jax.Array]:
    """Oracle for the fused masked kernel: (dists, masked ids) where padding
    and visited entries come back as (+inf, -1)."""
    masked = visited_mask_ref(ids, visited)
    return gather_distance_ref(queries, masked, base, metric), masked


def gather_adc_ref(ids: jax.Array, codes: jax.Array, luts: jax.Array) -> jax.Array:
    """ids (Q, R) into a code table (n, M) uint8, per-query LUTs (Q, M, K)
    -> (Q, R) ADC scores: score[q, r] = sum_m luts[q, m, codes[ids[q, r], m]].

    The compressed twin of ``gather_distance_ref``: padding ids (< 0) -> +inf.
    """
    rows = codes[jnp.maximum(ids, 0)].astype(jnp.int32)         # (Q, R, M)
    picked = jnp.take_along_axis(
        luts.astype(jnp.float32)[:, None], rows[..., None], axis=-1
    )[..., 0]                                                   # (Q, R, M)
    return jnp.where(ids >= 0, jnp.sum(picked, axis=-1), jnp.inf)


def gather_adc_masked_ref(
    ids: jax.Array, codes: jax.Array, luts: jax.Array, visited: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Oracle for the fused compressed kernel: (adc dists, masked ids) where
    padding and bitmap-visited entries come back as (+inf, -1)."""
    masked = visited_mask_ref(ids, visited)
    return gather_adc_ref(masked, codes, luts), masked


def gather_sq8_ref(
    queries: jax.Array,
    ids: jax.Array,
    codes: jax.Array,
    scale: jax.Array,
    mn: jax.Array,
    metric: str = "l2",
) -> jax.Array:
    """ids (Q, R) into an (n, d) uint8 scalar-quantized table with per-dim
    affine params scale/mn (d,) -> (Q, R) distances on the dequantized rows
    ``codes * scale + mn``.

    The 4x middle rung of the quantization ladder: d bytes fetched per
    scored vertex (vs 4d exact, M for PQ), full-rank geometry retained.
    Padding ids (< 0) produce +inf.
    """
    rows = codes[jnp.maximum(ids, 0)].astype(jnp.float32)       # (Q, R, d)
    rows = rows * scale.astype(jnp.float32) + mn.astype(jnp.float32)
    return _distances_from_rows(queries, ids, rows, metric)


def gather_sq8_masked_ref(
    queries: jax.Array,
    ids: jax.Array,
    codes: jax.Array,
    scale: jax.Array,
    mn: jax.Array,
    visited: jax.Array,
    metric: str = "l2",
) -> tuple[jax.Array, jax.Array]:
    """Oracle for the fused sq8 kernel: (dists, masked ids) where padding
    and bitmap-visited entries come back as (+inf, -1)."""
    masked = visited_mask_ref(ids, visited)
    return gather_sq8_ref(queries, masked, codes, scale, mn, metric), masked


def pq_adc_ref(codes: jax.Array, lut: jax.Array) -> jax.Array:
    """codes (n, M) uint8/int32, lut (M, K) f32 -> (n,) ADC scores.

    score[i] = sum_m lut[m, codes[i, m]]  (asymmetric distance computation).
    """
    m = jnp.arange(lut.shape[0])
    return jnp.sum(lut[m[None, :], codes.astype(jnp.int32)], axis=-1)


def flash_attention_ref(q, k, v, causal=True, window=None, softmax_scale=None):
    """Dense oracle for the flash kernel: q (B,S,Hq,dh), GQA-grouped."""
    B, S, Hq, dh = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else dh**-0.5
    qg = q.reshape(B, S, Hkv, G, dh).astype(jnp.float32) * scale
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32))
    pos = jnp.arange(S)
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= pos[:, None] >= pos[None, :]
    if window is not None:
        mask &= pos[:, None] - pos[None, :] < window
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, S, Hq, v.shape[-1]).astype(q.dtype)
