"""Fused gather + ADC Pallas kernel — the compressed beam-search inner loop.

The compressed twin of ``gather_distance_masked`` (DESIGN.md §8): instead of
fetching (R_tile, d) float rows and contracting against the query, fetch
(R_tile, M) uint8 PQ code rows from the HBM-resident (n, M) code table and
score them against the query's VMEM-resident (M, K) ADC lookup table —
M bytes of traffic per scored vertex instead of 4d.

Layout mirrors the exact kernel (``gather_distance.gather_scores``): grid =
(Q, R/R_tile), the code table stays in HBM (``pl.ANY``), each grid step
issues R_tile row-block DMAs (aligned 8-row blocks of the lane-padded table:
uint8 rows cannot be fetched alone) into a double-buffered VMEM scratch,
and the per-query LUT's BlockSpec revisits the same (1, M, K) block across
the inner tile loop. TPU has no fast per-lane gather, so the LUT lookup is
recast as one-hot matmuls (as in ``pq_adc``): each code column m becomes
lut[m] . onehot(codes[:, m]), a (1, K) x (R_tile, K) MXU contraction, K x M
MACs per row vs d for exact.

The masking contract is the exact kernel's: padding ids (< 0) and
bitmap-visited ids come back as (+inf, INVALID), so ``beam_search._step``
consumes (dists, masked ids) directly regardless of the scorer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .gather_distance import DEFAULT_R_TILE, gather_scores
from .ref import visited_mask_ref


def _adc_tile(codes, lut) -> jax.Array:
    """(R_tile, M) uint8 codes x (M, K) f32 LUT -> (1, R_tile) ADC scores."""
    M, K = lut.shape
    codes = codes.astype(jnp.int32)
    lut = lut.astype(jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, (codes.shape[0], K), 1)
    acc = jnp.zeros((1, codes.shape[0]), jnp.float32)
    for m in range(M):  # static unroll; M is 8/16
        onehot = (codes[:, m:m + 1] == col).astype(jnp.float32)
        # HIGHEST: a 0/1 x fp32 contraction is exact only without bf16
        # truncation of the LUT entries
        acc = acc + jax.lax.dot_general(
            lut[m:m + 1, :], onehot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
    return acc


@functools.partial(jax.jit, static_argnames=("r_tile", "interpret"))
def gather_adc_masked(
    ids: jax.Array,
    codes: jax.Array,
    luts: jax.Array,
    visited: jax.Array,
    r_tile: int = DEFAULT_R_TILE,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused code gather + ADC scoring + visited/validity masking.

    ids (Q, R) into codes (n, M) uint8, per-query LUTs (Q, M, K), visited the
    beam's (Q, ceil(n/32)) uint32 bitmap. Returns (adc dists (Q, R), masked
    ids (Q, R)): padding (< 0) or already-visited entries come back as
    (+inf, INVALID). Metric-agnostic — the LUT carries the metric
    (``baselines.pq.build_adc_luts``).
    """
    masked = visited_mask_ref(ids, visited)
    dists = gather_scores(_adc_tile, masked, codes, [luts], r_tile=r_tile,
                          interpret=interpret)
    return dists, masked
