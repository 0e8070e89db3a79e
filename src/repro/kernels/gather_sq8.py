"""Fused gather + scalar-quantized distance Pallas kernel (DESIGN.md §15).

The 4x middle rung of the quantization ladder: the base is stored as an
(n, d) uint8 table with per-dimension affine dequantization params
(``scale``/``mn``, each (d,)), so a scored vertex costs d bytes of HBM
traffic instead of 4d (exact) while keeping full-rank geometry — unlike PQ
there is no subspace factorization, so recall sits between exact and pq at
every d (the property ``pq_sweep`` tracks).

Layout is the exact kernel's (``gather_distance.gather_scores``): grid =
(Q, R/R_tile), the uint8 table stays in HBM (``pl.ANY``), each grid step
issues R_tile row-block DMAs (aligned 8-row blocks: uint8 rows cannot be
fetched alone) into a double-buffered VMEM scratch,
dequantizes the tile on the VPU (one fused multiply-add against the
VMEM-resident (1, d) scale/min rows), and reduces against the query with the
same MXU contraction + metric epilogue as the float kernel.

The masking contract is shared too: padding ids (< 0) and bitmap-visited
ids come back as (+inf, INVALID), so ``beam_search._step`` consumes
(dists, masked ids) directly regardless of the scorer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .gather_distance import (DEFAULT_R_TILE, _tile_distances, gather_scores,
                              lane_pad)
from .ref import visited_mask_ref


def _sq8_tile(metric):
    def score(codes, q, scale, mn):
        rows = codes.astype(jnp.int32).astype(jnp.float32) * scale + mn
        return _tile_distances(q.astype(jnp.float32), rows, metric)
    return score


@functools.partial(
    jax.jit, static_argnames=("metric", "r_tile", "interpret")
)
def gather_sq8_masked(
    queries: jax.Array,
    ids: jax.Array,
    codes: jax.Array,
    scale: jax.Array,
    mn: jax.Array,
    visited: jax.Array,
    metric: str = "l2",
    r_tile: int = DEFAULT_R_TILE,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused uint8 row gather + dequantized distance + visited/validity mask.

    ids (Q, R) into codes (n, d) uint8 with dequant params scale/mn (d,),
    visited the beam's (Q, ceil(n/32)) uint32 bitmap. Returns
    (dists (Q, R), masked ids (Q, R)): padding (< 0) or already-visited
    entries come back as (+inf, INVALID).
    """
    Q, d = queries.shape
    masked = visited_mask_ref(ids, visited)
    dists = gather_scores(
        _sq8_tile(metric), masked, codes,
        [lane_pad(queries).reshape(Q, 1, -1)],
        [lane_pad(jnp.asarray(scale, jnp.float32).reshape(1, d)),
         lane_pad(jnp.asarray(mn, jnp.float32).reshape(1, d))],
        r_tile=r_tile, interpret=interpret,
    )
    return dists, masked
