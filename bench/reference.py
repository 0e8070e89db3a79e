"""The plain reference: exact top-k and exact distances, in blocked
``jax.numpy`` at full float32 precision.

It imports nothing of the program and takes none of its arrays: the harness
hands it the corpus as ``gen.corpus`` makes it and the query rows it sent.
Distances follow the program's documented metric semantics (smaller is
closer): ``l2`` is the squared euclidean distance, ``cos`` is one minus the
cosine similarity, ``ip`` the negated inner product.

``dtype`` is the precision the distances are computed in: ``float32`` (at
``Precision.HIGHEST``) for the reference, ``bfloat16`` for the control that
must fail the comparison (``bench/control.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
CORPUS_BLOCK = 1 << 17
QUERY_BLOCK = 1024


def _cast(x, dtype):
    return x.astype(jnp.bfloat16) if dtype == "bfloat16" else x


def _cross(q, x, dtype):
    return jax.lax.dot_general(
        _cast(q, dtype), _cast(x, dtype), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=HIGHEST)


def _sqnorm(x, dtype):
    x = _cast(x, dtype).astype(jnp.float32)
    return jnp.sum(x * x, axis=-1)


def block_distances(q, x, metric: str, dtype: str = "float32"):
    """(Q, d) x (B, d) -> (Q, B) distances."""
    cross = _cross(q, x, dtype)
    if metric == "ip":
        return -cross
    qq, xx = _sqnorm(q, dtype)[:, None], _sqnorm(x, dtype)[None, :]
    if metric == "cos":
        return 1.0 - cross / jnp.sqrt(jnp.maximum(qq * xx, 1e-24))
    if metric == "l2":
        return jnp.maximum(qq - 2.0 * cross + xx, 0.0)
    raise ValueError(f"unknown metric {metric!r}")


@functools.partial(jax.jit, static_argnames=("k", "metric", "dtype"))
def _merge_block(best_d, best_i, q, x, offset, k, metric, dtype):
    d = block_distances(q, x, metric, dtype)
    neg, pos = jax.lax.top_k(-d, min(k, d.shape[1]))
    cand_d = jnp.concatenate([best_d, -neg], axis=1)
    cand_i = jnp.concatenate([best_i, pos.astype(jnp.int32) + offset], axis=1)
    neg, sel = jax.lax.top_k(-cand_d, k)
    return -neg, jnp.take_along_axis(cand_i, sel, axis=1)


def exact_topk(queries, base, k: int, metric: str, dtype: str = "float32"):
    """Exact k nearest rows of ``base`` (n, d) to each of ``queries`` (Q, d):
    host arrays (ids (Q, k) int32, dists (Q, k) float32), ascending. The
    corpus is scanned in blocks of ``CORPUS_BLOCK`` rows, the queries in
    blocks of ``QUERY_BLOCK``, so the peak stays a few hundred MB."""
    base = jnp.asarray(base, jnp.float32)
    queries = np.asarray(queries, np.float32)
    n = base.shape[0]
    out_i, out_d = [], []
    for lo in range(0, queries.shape[0], QUERY_BLOCK):
        q = jnp.asarray(queries[lo:lo + QUERY_BLOCK])
        best_d = jnp.full((q.shape[0], k), jnp.inf, jnp.float32)
        best_i = jnp.full((q.shape[0], k), -1, jnp.int32)
        for off in range(0, n, CORPUS_BLOCK):
            best_d, best_i = _merge_block(best_d, best_i, q,
                                          base[off:off + CORPUS_BLOCK],
                                          jnp.int32(off), k, metric, dtype)
        out_i.append(np.asarray(best_i))
        out_d.append(np.asarray(best_d))
    return np.concatenate(out_i), np.concatenate(out_d)


@functools.partial(jax.jit, static_argnames=("metric",))
def _pair(q, rows, metric):
    if metric == "l2":
        diff = q[:, None, :] - rows
        return jnp.sum(diff * diff, axis=-1)
    cross = jnp.einsum("qd,qkd->qk", q, rows, precision=HIGHEST)
    if metric == "ip":
        return -cross
    qq = jnp.sum(q * q, axis=-1)[:, None]
    xx = jnp.sum(rows * rows, axis=-1)
    return 1.0 - cross / jnp.sqrt(jnp.maximum(qq * xx, 1e-24))


@functools.partial(jax.jit, static_argnames=("metric",))
def _scale(q, rows, metric):
    """The size a distance's rounding is measured against: |q|^2 + |x|^2 for
    l2 (the magnitude the expanded form cancels), |q||x| for ip, 1 for cos."""
    qq = jnp.sum(q * q, axis=-1)[:, None]
    xx = jnp.sum(rows * rows, axis=-1)
    if metric == "l2":
        return qq + xx
    if metric == "ip":
        return jnp.sqrt(qq * xx)
    return jnp.ones_like(xx)


def pair_distances(queries, base, ids, metric: str):
    """Exact distance of each query (Q, d) to its listed ids (Q, k), and the
    scale of each: host arrays (Q, k). Ids < 0 give NaN."""
    base = jnp.asarray(base, jnp.float32)
    ids = np.asarray(ids)
    safe = jnp.asarray(np.where(ids < 0, 0, ids), jnp.int32)
    q = jnp.asarray(np.asarray(queries, np.float32))
    rows = base[safe]
    d = np.array(_pair(q, rows, metric))
    s = np.asarray(_scale(q, rows, metric))
    d[ids < 0] = np.nan
    return d, s
