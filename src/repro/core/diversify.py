"""Graph diversification — the paper's hybrid scheme (Sec. III/IV).

Two strategies over the same flat k-NN graph:

* **GD** (HNSW's occlusion heuristic, paper Fig. 2): keep candidate c iff
  d(v,c) < d(s,c) for every already-kept s; at most L/2 survivors; then union
  with reverse edges ("KGraph+GD").
* **DPG** [Li TKDE'19]: angular max-min diversification — greedily keep the
  candidate whose minimum angle to the kept set is largest, L/2 keeps, then
  union with reverse edges.

Both are vectorized: per-vertex candidate geometry is a (L, L) matrix
(distances for GD, angle cosines for DPG) computed in chunks, and the greedy
selection is a lax.fori over L with a kept-mask carry, vmapped over vertices.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .graph_index import KnnGraph
from .topk import INVALID, sort_by_distance


# -- reverse-edge union -------------------------------------------------------


class ReverseUnionStats(NamedTuple):
    """Edge accounting of one reverse-edge union (BuildReport currency).

    candidates   : valid forward edges = reverse-edge candidates offered
    dropped_slot : candidates that overflowed the r reverse slots a target
                   row reserves (the scatter's fixed-shape bound)
    dropped_cap  : surviving unique ids evicted by the final max_degree
                   truncation (forward or reverse — both count: they are
                   edges the unbounded paper union would have kept)
    """

    candidates: int
    dropped_slot: int
    dropped_cap: int

    @property
    def dropped(self) -> int:
        return self.dropped_slot + self.dropped_cap


def add_reverse_edges_with_stats(
    neighbors: jax.Array, max_degree: int
) -> tuple[jax.Array, ReverseUnionStats]:
    """Union adjacency with its reverse edges, capped at max_degree.

    Slot assignment is deterministic: incoming edges are ranked by source id
    (sort + cumcount) so rebuilds are reproducible; overflow beyond the cap is
    dropped (the paper takes the plain union; we bound the degree for fixed
    shapes). The returned :class:`ReverseUnionStats` counts every dropped
    edge — ``BuildReport`` surfaces them next to the realized degree
    distribution so a too-tight cap is visible, not silent.
    """
    compact, n_valid, n_kept, n_cap = _reverse_union(neighbors, max_degree)
    stats = ReverseUnionStats(
        candidates=int(n_valid),
        dropped_slot=int(n_valid) - int(n_kept),
        dropped_cap=int(n_cap),
    )
    return compact, stats


@functools.partial(jax.jit, static_argnames=("max_degree",))
def _reverse_union(neighbors, max_degree: int):
    """(capped union, valid forward edges, kept reverse slots, cap
    evictions) — the device half of :func:`add_reverse_edges_with_stats`."""
    n, r = neighbors.shape
    src = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], (n, r)).ravel()
    tgt = neighbors.ravel()
    valid = tgt >= 0
    tgt_s = jnp.where(valid, tgt, n)  # invalid edges sort to a scratch row

    order = jnp.argsort(tgt_s, stable=True)
    tgt_sorted, src_sorted = tgt_s[order], src[order]
    # first occurrence position of each target = scatter-min of positions
    pos = jnp.arange(tgt_sorted.shape[0], dtype=jnp.int32)
    first = jnp.full((n + 1,), jnp.iinfo(jnp.int32).max, jnp.int32)
    first = first.at[tgt_sorted].min(pos)
    slot = pos - first[tgt_sorted]

    n_rev = r  # reserve up to r reverse slots per vertex before the cap
    keep = (slot < n_rev) & (tgt_sorted < n)
    rev = jnp.full((n + 1, n_rev), INVALID, jnp.int32)
    rev = rev.at[
        jnp.where(keep, tgt_sorted, n), jnp.where(keep, slot, 0)
    ].set(jnp.where(keep, src_sorted, INVALID), mode="drop")
    rev = rev[:n]

    merged = jnp.concatenate([neighbors, rev], axis=1)
    # dedup by id per row (distance-free): sort ids, mask repeats, compact by
    # moving INVALID to the end via argsort on (is_invalid, original position).
    ids_sorted = jnp.sort(merged, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((n, 1), bool), ids_sorted[:, 1:] == ids_sorted[:, :-1]], axis=1
    )
    ids_sorted = jnp.where(dup | (ids_sorted < 0), INVALID, ids_sorted)
    key = jnp.where(ids_sorted == INVALID, jnp.iinfo(jnp.int32).max, 0)
    order2 = jnp.argsort(key, axis=1, stable=True)
    compact = jnp.take_along_axis(ids_sorted, order2, axis=1)
    return (compact[:, :max_degree], valid.sum(), keep.sum(),
            (compact[:, max_degree:] != INVALID).sum())


def add_reverse_edges(neighbors: jax.Array, max_degree: int) -> jax.Array:
    """Reverse-edge union without the accounting — see
    :func:`add_reverse_edges_with_stats` (same adjacency, bit-identical)."""
    merged, _ = add_reverse_edges_with_stats(neighbors, max_degree)
    return merged


# -- GD: occlusion pruning (HNSW heuristic) -----------------------------------


def _occlusion_select(cand_d: jax.Array, pair_d: jax.Array, valid: jax.Array,
                      max_keep: int) -> jax.Array:
    """One vertex: cand_d (L,) sorted asc, pair_d (L, L), -> keep mask (L,)."""
    L = cand_d.shape[0]

    def body(j, state):
        keep, count = state
        # occluded if some kept s has d(s, c_j) <= d(v, c_j)
        occluded = jnp.any(keep & (pair_d[:, j] <= cand_d[j]))
        ok = valid[j] & (~occluded) & (count < max_keep)
        return keep.at[j].set(ok), count + ok.astype(jnp.int32)

    keep, _ = jax.lax.fori_loop(
        0, L, body, (jnp.zeros((L,), bool), jnp.int32(0))
    )
    return keep


@functools.partial(jax.jit, static_argnames=("max_keep", "metric", "chunk"))
def gd_prune(
    base: jax.Array,
    graph: KnnGraph,
    max_keep: int | None = None,
    metric: str = "l2",
    chunk: int = 512,
) -> jax.Array:
    """HNSW-heuristic pruning of a flat graph; returns (n, L) ids, -1 padded,
    with at most ``max_keep`` (default L/2, per the paper) kept per vertex."""
    from repro.kernels import ops

    n, L = graph.neighbors.shape
    if max_keep is None:
        max_keep = L // 2
    dists, ids = sort_by_distance(graph.dists, graph.neighbors)

    pad = (-n) % chunk
    ids_p = jnp.concatenate([ids, jnp.full((pad, L), INVALID, jnp.int32)]) if pad else ids
    d_p = jnp.concatenate([dists, jnp.full((pad, L), jnp.inf)]) if pad else dists

    def tile(args):
        tids, tds = args  # (chunk, L)
        rows = base[jnp.maximum(tids, 0)]  # (chunk, L, d)
        # pairwise distances among the candidates of each vertex
        def pair(mat, row_ids):
            pd = ops.distance_matrix(mat, mat, metric=metric)
            bad = (row_ids < 0)[:, None] | (row_ids < 0)[None, :]
            return jnp.where(bad, jnp.inf, pd)

        pair_d = jax.vmap(pair)(rows, tids)  # (chunk, L, L)
        valid = tids >= 0
        return jax.vmap(_occlusion_select, in_axes=(0, 0, 0, None))(
            tds, pair_d, valid, max_keep
        )

    keep = jax.lax.map(
        tile, (ids_p.reshape(-1, chunk, L), d_p.reshape(-1, chunk, L))
    ).reshape(-1, L)[:n]
    kept_ids = jnp.where(keep, ids, INVALID)
    # compact kept entries to the front (they are distance-sorted already)
    order = jnp.argsort(~keep, axis=1, stable=True)
    return jnp.take_along_axis(kept_ids, order, axis=1)


def build_gd_graph(
    base: jax.Array,
    graph: KnnGraph,
    metric: str = "l2",
    max_keep: int | None = None,
    max_degree: int | None = None,
) -> KnnGraph:
    """The paper's hybrid scheme: GD prune + reverse-edge union (KGraph+GD)."""
    L = graph.degree
    kept = gd_prune(base, graph, max_keep=max_keep, metric=metric)
    merged = add_reverse_edges(kept, max_degree or L)
    return KnnGraph(neighbors=merged, dists=jnp.full(merged.shape, jnp.nan))


# -- DPG: angular diversification ---------------------------------------------


def _angular_select(cos_sim: jax.Array, valid: jax.Array, max_keep: int) -> jax.Array:
    """Greedy max-min angular selection for one vertex.

    cos_sim (L, L): cosine similarity between edge directions (c_i - v).
    Keeps the candidate whose max similarity to the kept set is smallest
    (equivalently max-min angle), seeded with the nearest valid candidate.
    """
    L = cos_sim.shape[0]
    seed = jnp.argmax(valid)  # candidates arrive distance-sorted
    keep = jnp.zeros((L,), bool).at[seed].set(valid[seed])

    def body(_, keep):
        # max similarity of each candidate to the kept set
        sim_to_kept = jnp.max(jnp.where(keep[None, :], cos_sim, -jnp.inf), axis=1)
        score = jnp.where(valid & ~keep, sim_to_kept, jnp.inf)
        j = jnp.argmin(score)
        ok = score[j] < jnp.inf
        return keep.at[j].set(keep[j] | ok)

    return jax.lax.fori_loop(1, max_keep, body, keep)


@functools.partial(jax.jit, static_argnames=("max_keep", "chunk"))
def dpg_prune(
    base: jax.Array, graph: KnnGraph, max_keep: int | None = None, chunk: int = 512
) -> jax.Array:
    n, L = graph.neighbors.shape
    if max_keep is None:
        max_keep = L // 2
    dists, ids = sort_by_distance(graph.dists, graph.neighbors)

    pad = (-n) % chunk
    ids_p = jnp.concatenate([ids, jnp.full((pad, L), INVALID, jnp.int32)]) if pad else ids
    vid = jnp.arange(n + pad, dtype=jnp.int32)

    def tile(args):
        rows_v, tids = args
        v = base[jnp.minimum(rows_v, n - 1)]  # (chunk, d)
        c = base[jnp.maximum(tids, 0)]  # (chunk, L, d)
        e = c - v[:, None, :]
        e = e * jax.lax.rsqrt(jnp.maximum(jnp.sum(e * e, -1, keepdims=True), 1e-12))
        cs = jnp.einsum("cld,cmd->clm", e, e)
        valid = tids >= 0
        return jax.vmap(_angular_select, in_axes=(0, 0, None))(cs, valid, max_keep)

    keep = jax.lax.map(
        tile, (vid.reshape(-1, chunk), ids_p.reshape(-1, chunk, L))
    ).reshape(-1, L)[:n]
    kept_ids = jnp.where(keep, ids, INVALID)
    order = jnp.argsort(~keep, axis=1, stable=True)
    return jnp.take_along_axis(kept_ids, order, axis=1)


def build_dpg_graph(
    base: jax.Array,
    graph: KnnGraph,
    max_keep: int | None = None,
    max_degree: int | None = None,
) -> KnnGraph:
    """DPG = angular diversification + reverse edges [Li TKDE'19]."""
    L = graph.degree
    kept = dpg_prune(base, graph, max_keep=max_keep)
    # DPG keeps the full union (its index is ~2x GD's size; the paper calls
    # this out) — default cap 2x the kept degree.
    merged = add_reverse_edges(kept, max_degree or 2 * (max_keep or L // 2))
    return KnnGraph(neighbors=merged, dists=jnp.full(merged.shape, jnp.nan))
