"""Fused gather + distance Pallas kernel — the beam-search inner loop.

Given per-query neighbor ids, fetch the base rows straight from HBM and reduce
against the query without materializing a (Q, R, d) intermediate in HBM.

Tiled layout (DESIGN.md §7): grid = (Q, R/R_tile), run in order. The base
stays in HBM (``pl.ANY``); each grid step issues ``R_tile`` row DMAs into a
double-buffered VMEM scratch — the fetch for the next step (the next tile of
this query, or the first tile of the next query) is in flight while this tile
reduces — and the query row stays VMEM-resident across the inner tile loop
(its BlockSpec revisits the same block). The reduction is one (1, d) x
(R_tile, d) contraction on the MXU instead of R scalar (1, d) dot-sums.

Mosaic accepts a block only when its last two dims are multiples of (8, 128)
or equal the array's, so every per-query operand is laid out with a unit
second-minor dim: ids and distances as (Q, R/R_tile, 1, R_tile), query rows
as (Q, 1, d), LUTs as (Q, M, K). The ids are also scalar-prefetched into
SMEM (flat, to address the row DMAs); a batch whose ids would crowd SMEM is
split into several calls along Q (``PREFETCH_IDS``).

A DMA out of HBM must cover whole (8, 128) tiles of the table, with one
exception: a single row of a 128-wide fp32 table (SIFT's d=128 base). Every
other table (uint8 codes, other widths) is padded to a multiple of 128
columns and fetched as the aligned 8-row block holding the wanted row; the
tile is scored whole and a 0/1 selection contraction keeps each id's own row.
That costs 8x the bytes of a row fetch (``row_block``).

``gather_scores`` is that plumbing, shared by the three scorers' kernels
(exact here, ``gather_sq8`` and ``gather_adc``): each supplies only its tile
score. Padding ids (< 0) score +inf in the kernel. The ``*_masked`` entry
points test the (Q, R) ids against the beam's bit-packed visited bitmap in
XLA first (``ref.visited_mask_ref``) and hand the kernel the pre-masked ids,
returning (dists, masked ids) with the (+inf, INVALID) contract, so
``beam_search._step`` consumes kernel outputs directly.
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import visited_mask_ref

DEFAULT_R_TILE = 16
# Most ids one pallas_call scalar-prefetches (256 KiB of int32). SMEM holds
# 1 MiB; NN-Descent's (1024, 240) scoring tile alone would fill it.
PREFETCH_IDS = 1 << 16


def _ceil_to(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def _tile_distances(q, rows, metric: str) -> jax.Array:
    """(1, d) query x (R_tile, d) rows -> (1, R_tile) distances, fp32.

    One MXU contraction for the cross term; norms fused on the VPU."""
    # HIGHEST keeps the MXU passes full fp32: the l2/cos epilogues difference
    # large norms, so bf16-truncated products would cancel catastrophically
    # for near-duplicate rows.
    cross = jax.lax.dot_general(
        q, rows, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # (1, R_tile)
    if metric == "ip":
        return -cross
    rr = jnp.sum(rows * rows, axis=-1)[None, :]  # (1, R_tile)
    if metric == "cos":
        qn = jax.lax.rsqrt(jnp.maximum(jnp.sum(q * q), 1e-12))
        return 1.0 - cross * qn * jax.lax.rsqrt(jnp.maximum(rr, 1e-12))
    qq = jnp.sum(q * q)
    return jnp.maximum(qq - 2.0 * cross + rr, 0.0)


def row_block(table) -> int:
    """Rows per id-fetch DMA for ``table``: 1 where Mosaic can slice a single
    row out of HBM (a 128-wide fp32 table), else the aligned 8-row block."""
    return 1 if table.dtype == jnp.float32 and table.shape[1] == 128 else 8


def lane_pad(x: jax.Array) -> jax.Array:
    """Zero-pad the last dim to a multiple of 128 lanes (a no-op at d=128)."""
    pad = (-x.shape[-1]) % 128
    if not pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def fetch_rows_double_buffered(ids_sref, src_ref, rows, sems, r_tile: int,
                               blk: int):
    """Scattered-row double buffering: on grid step (q, t), prefetch the NEXT
    step's ``r_tile`` DMAs (each the ``blk``-row block holding one id's row)
    from HBM ``src_ref`` into the alternate VMEM buffer, drain this step's,
    and return the scratch slot holding its rows. ``ids_sref`` is the flat
    (Q * R_pad,) id list in SMEM; the next step may belong to the next
    query, which is why the grid must run in order."""
    qi, t = pl.program_id(0), pl.program_id(1)
    nt = pl.num_programs(1)
    step = qi * nt + t
    last = pl.num_programs(0) * nt - 1

    def row_dma(slot, j, flat_step):
        rid = jnp.maximum(ids_sref[flat_step * r_tile + j], 0)
        if blk > 1:
            rid = pl.multiple_of(rid // blk * blk, blk)
        return pltpu.make_async_copy(
            src_ref.at[pl.ds(rid, blk), :],
            rows.at[slot, pl.ds(j * blk, blk), :],
            sems.at[slot, j],
        )

    def start_fetch(slot, flat_step):
        for j in range(r_tile):
            row_dma(slot, j, flat_step).start()

    # step 0 warms up; every step prefetches the next into the alternate
    # buffer before draining its own.
    @pl.when(step == 0)
    def _():
        start_fetch(0, 0)

    @pl.when(step < last)
    def _():
        start_fetch((step + 1) % 2, step + 1)

    slot = step % 2
    for j in range(r_tile):
        row_dma(slot, j, step).wait()
    return slot


def _select_rows(d, ids, blk: int):
    """(1, R_tile * blk) block scores -> (1, R_tile): keep, for each id, the
    score of its own row in its fetched block (an exact 0/1 contraction)."""
    n_rows, rt = d.shape[1], ids.shape[1]
    i = jax.lax.broadcasted_iota(jnp.int32, (n_rows, rt), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (n_rows, rt), 1)
    sel = (i == j * blk + (ids & (blk - 1))).astype(jnp.float32)
    return jax.lax.dot_general(
        d, sel, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


def _gather_kernel(ids_sref, idv_ref, *refs, score_tile, n_query, n_shared,
                   r_tile, blk):
    q_refs = refs[:n_query]
    s_refs = refs[n_query:n_query + n_shared]
    table_ref, out_ref, rows, sems = refs[n_query + n_shared:]
    slot = fetch_rows_double_buffered(ids_sref, table_ref, rows, sems, r_tile,
                                      blk)
    tile = rows[pl.ds(slot, 1)][0]                       # (R_tile * blk, w)
    d = score_tile(tile, *[r[0] for r in q_refs], *[r[...] for r in s_refs])
    ids = idv_ref[0, 0]                                  # (1, R_tile)
    if blk > 1:
        d = _select_rows(d, ids, blk)
    out_ref[0, 0] = jnp.where(ids < 0, jnp.inf, d)


def _gather_call(score_tile, ids, table, q_ops, shared_ops, rt, blk,
                 interpret):
    Q, R = ids.shape
    Rp = _ceil_to(R, rt)
    nt = Rp // rt
    if Rp != R:
        ids = jnp.pad(ids, ((0, 0), (0, Rp - R)), constant_values=-1)
    q_specs = [
        pl.BlockSpec((1,) + x.shape[1:],
                     lambda q, t, ids, nd=x.ndim: (q,) + (0,) * (nd - 1))
        for x in q_ops
    ]
    s_specs = [
        pl.BlockSpec(x.shape, lambda q, t, ids, nd=x.ndim: (0,) * nd)
        for x in shared_ops
    ]
    tile_spec = pl.BlockSpec((1, 1, 1, rt), lambda q, t, ids: (q, t, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Q, nt),
        in_specs=[tile_spec, *q_specs, *s_specs,
                  pl.BlockSpec(memory_space=pl.ANY)],       # table, HBM
        out_specs=tile_spec,
        scratch_shapes=[
            pltpu.VMEM((2, rt * blk, table.shape[1]), table.dtype),
            pltpu.SemaphoreType.DMA((2, rt)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_gather_kernel, score_tile=score_tile,
                          n_query=len(q_ops), n_shared=len(shared_ops),
                          r_tile=rt, blk=blk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Q, nt, 1, rt), jnp.float32),
        interpret=interpret,
    )(ids.reshape(-1), ids.reshape(Q, nt, 1, rt), *q_ops, *shared_ops, table)
    return out.reshape(Q, Rp)[:, :R]


def gather_scores(
    score_tile: Callable,
    ids: jax.Array,
    table: jax.Array,
    q_ops: Sequence[jax.Array] = (),
    shared_ops: Sequence[jax.Array] = (),
    *,
    r_tile: int = DEFAULT_R_TILE,
    interpret: bool = False,
) -> jax.Array:
    """Score ids (Q, R) against rows of ``table`` (n, w), which stays in HBM.

    ``score_tile(rows (m, w'), *query_blocks, *shared_blocks)`` returns the
    (1, m) fp32 scores of m fetched rows, where w' is w padded to a multiple
    of 128 lanes (``lane_pad``: operands that meet the rows must be padded
    alike). ``q_ops`` are per-query arrays (Q, a, b) — the kernel sees query
    q's (a, b) block; ``shared_ops`` are whole arrays every grid step sees.
    Padding ids (< 0) score +inf."""
    Q, R = ids.shape
    rt = max(1, min(r_tile, R))
    blk = row_block(table)
    table = lane_pad(table)
    if table.shape[0] % blk:
        table = jnp.pad(table, ((0, (-table.shape[0]) % blk), (0, 0)))
    per_call = max(1, PREFETCH_IDS // _ceil_to(R, rt))
    outs = [
        _gather_call(score_tile, ids[lo:lo + per_call], table,
                     [x[lo:lo + per_call] for x in q_ops], shared_ops, rt,
                     blk, interpret)
        for lo in range(0, Q, per_call)
    ]
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)


def _exact_tile(metric):
    def score(rows, q):
        return _tile_distances(q.astype(jnp.float32),
                               rows.astype(jnp.float32), metric)
    return score


@functools.partial(
    jax.jit, static_argnames=("metric", "r_tile", "interpret")
)
def gather_distance(
    queries: jax.Array,
    ids: jax.Array,
    base: jax.Array,
    metric: str = "l2",
    r_tile: int = DEFAULT_R_TILE,
    interpret: bool = False,
) -> jax.Array:
    """queries (Q, d), ids (Q, R), base (n, d) -> (Q, R) distances."""
    Q, d = queries.shape
    return gather_scores(_exact_tile(metric), ids, base,
                         [lane_pad(queries).reshape(Q, 1, -1)],
                         r_tile=r_tile, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("metric", "r_tile", "interpret")
)
def gather_distance_masked(
    queries: jax.Array,
    ids: jax.Array,
    base: jax.Array,
    visited: jax.Array,
    metric: str = "l2",
    r_tile: int = DEFAULT_R_TILE,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused gather + distance + visited/validity masking.

    visited is the beam's (Q, ceil(n/32)) uint32 bitmap. Returns
    (dists (Q, R), masked ids (Q, R)): entries that are padding (< 0) or
    already visited come back as (+inf, INVALID), so the caller never
    re-masks in XLA.
    """
    masked = visited_mask_ref(ids, visited)
    return gather_distance(queries, masked, base, metric=metric,
                           r_tile=r_tile, interpret=interpret), masked
