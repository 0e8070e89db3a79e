"""NN-Descent (KGraph) — approximate k-NN graph construction [Dong WWW'11].

TPU-native restructuring (DESIGN.md §2): the per-vertex hash-set local join of
the CPU algorithm becomes fixed-shape rounds:

  1. sample S neighbors per vertex (new-biased, as in the original),
  2. expand to neighbor-of-neighbor candidates (S x S2 ids per vertex),
  3. add reverse-edge candidates via a random-slot scatter (collisions drop
     entries — NN-Descent is stochastic already; recall is validated in tests),
  4. score all candidates with the fused gather+distance kernel, chunked so
     the (chunk, C, d) gather stays inside VMEM-scale working sets, and push
     each vertex's K best scored edges back to their other ends,
  5. merge into the sorted K-list with fixed-shape dedup.

The update counter gives the standard early-termination rule (delta * n * K).

A round's phases run under ``jax.named_scope``s that name them in a device
trace: ``nd_join`` (1-3), ``nd_score`` (4) and ``nd_merge`` (4b-5); the
random start is ``nd_init``. Each round on the host is an ``ann.nd_round``
profiler span.
"""
from __future__ import annotations

import functools
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .graph_index import KnnGraph
from .topk import INVALID, dedup_by_id

# ---------------------------------------------------------------------------


class NNDescentConfig(NamedTuple):
    k: int = 20          # neighbors kept per vertex (paper: "several tens")
    sample: int = 12     # S: sampled neighbors for the local join
    sample_nn: int = 12  # S2: sampled entries of each sampled neighbor's list
    reverse: int = 24    # reverse-edge candidate slots
    rounds: int = 15
    delta: float = 0.002  # stop when update-rate < delta
    chunk: int = 1024    # vertices scored per inner tile


def _random_init(key: jax.Array, n: int, k: int) -> jax.Array:
    """k distinct random neighbors per vertex (self allowed then masked)."""
    # Vectorized: random ints, self/dup handled by the first merge round.
    ids = jax.random.randint(key, (n, k), 0, n, dtype=jnp.int32)
    self_ids = jnp.arange(n, dtype=jnp.int32)[:, None]
    ids = jnp.where(ids == self_ids, (ids + 1) % n, ids)
    return ids


def _score_chunked(base, pool, metric, chunk):
    """pool (n, C) ids -> (n, C) distances to each row's own vertex, tiled."""
    from repro.kernels import ops

    n, C = pool.shape
    pad = (-n) % chunk
    if pad:
        pool = jnp.concatenate([pool, jnp.full((pad, C), INVALID, jnp.int32)])
    vid = jnp.arange(n + pad, dtype=jnp.int32)

    def tile(args):
        rows, ids = args
        q = base[jnp.minimum(rows, n - 1)]
        return ops.gather_distance(q, ids, base, metric=metric)

    dists = jax.lax.map(
        tile,
        (vid.reshape(-1, chunk), pool.reshape(-1, chunk, C)),
    ).reshape(n + pad, C)
    return dists[:n]


def _round(base, ids, dists, isnew, key, cfg: NNDescentConfig, metric: str):
    n, k = ids.shape

    with jax.named_scope("nd_join"):
        kf, kr, ks = jax.random.split(key, 3)
        # -- 1. new-biased sampling of own neighbors --------------------------
        # Priority = random, boosted for new entries; take top-S positions.
        prio = jax.random.uniform(kf, (n, k)) + isnew.astype(jnp.float32)
        sel = jnp.argsort(-prio, axis=-1)[:, : cfg.sample]            # (n, S)
        nbr = jnp.take_along_axis(ids, sel, axis=-1)                   # (n, S)

        # -- 2. neighbor-of-neighbor expansion --------------------------------
        safe_nbr = jnp.maximum(nbr, 0)
        nn_lists = ids[safe_nbr]                                   # (n, S, k)
        cols = jax.random.randint(ks, (n, cfg.sample, cfg.sample_nn), 0, k)
        nn_cand = jnp.take_along_axis(nn_lists, cols, axis=-1)    # (n, S, S2)
        nn_cand = jnp.where(nbr[..., None] >= 0, nn_cand, INVALID)
        nn_cand = nn_cand.reshape(n, -1)

        # -- 3. reverse-edge candidates (random-slot scatter) -----------------
        src = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], (n, k))
        tgt = ids
        slots = jax.random.randint(kr, (n, k), 0, cfg.reverse)
        rev = jnp.full((n, cfg.reverse), INVALID, jnp.int32)
        valid = tgt >= 0
        rev = rev.at[jnp.where(valid, tgt, 0).ravel(), slots.ravel()].set(
            jnp.where(valid, src, INVALID).ravel(), mode="drop"
        )
        # Also join reverse candidates' neighborhoods (one hop), sampled:
        rev_sel = rev[:, : max(2, cfg.reverse // 4)]
        rev_nn = ids[jnp.maximum(rev_sel, 0)][..., : cfg.sample_nn]
        rev_nn = jnp.where(rev_sel[..., None] >= 0, rev_nn,
                           INVALID).reshape(n, -1)

        pool = jnp.concatenate([nn_cand, rev, rev_nn], axis=1)         # (n, C)
        pool = jnp.where(pool == jnp.arange(n, dtype=jnp.int32)[:, None],
                         INVALID, pool)

    # -- 4. score ------------------------------------------------------------
    with jax.named_scope("nd_score"):
        cand_d = _score_chunked(base, pool, metric, cfg.chunk)

    with jax.named_scope("nd_merge"):
        # -- 4b. symmetric push-back: the original local join updates BOTH
        # ends of a compared pair. Scatter each vertex's k best scored edges
        # (v -> c, d) into c's incoming buffer (random slot, collisions drop)
        # and merge them too. Pushing the k best rather than all C candidates
        # keeps the edges that can enter c's list and cuts the scatter (which
        # XLA:TPU lowers to a sort of its indices) from n*C to n*k updates.
        # One scatter of the edge's flat position carries both ends: (id,
        # dist) pairs stay consistent whichever writer wins a slot.
        _, best = jax.lax.top_k(-cand_d, k)
        push_pool = jnp.take_along_axis(pool, best, axis=1)            # (n, k)
        push_cand = jnp.take_along_axis(cand_d, best, axis=1)
        C = push_pool.shape[1]
        kp = jax.random.fold_in(key, 7)
        rb = max(k, cfg.reverse)
        pslots = jax.random.randint(kp, (n, C), 0, rb)
        pvalid = (push_pool >= 0) & jnp.isfinite(push_cand)
        flat_tgt = jnp.where(pvalid, push_pool, 0).ravel()
        pos = jnp.arange(n * C, dtype=jnp.int32)
        push_pos = jnp.full((n, rb), INVALID, jnp.int32).at[
            flat_tgt, pslots.ravel()
        ].set(jnp.where(pvalid.ravel(), pos, INVALID), mode="drop")
        pushed = push_pos >= 0
        push_i = jnp.where(pushed, push_pos // C, INVALID)
        push_d = jnp.where(pushed, push_cand.ravel()[jnp.maximum(push_pos, 0)],
                           jnp.inf)

        # -- 5. merge ---------------------------------------------------------
        def merge(row_d, row_i, cd, ci, pd, pi):
            d, i = dedup_by_id(
                jnp.concatenate([row_d, cd, pd]),
                jnp.concatenate([row_i, ci, pi]),
            )
            return d[:k], i[:k]

        new_d, new_i = jax.vmap(merge)(dists, ids, cand_d, pool, push_d,
                                       push_i)
        # an entry is "new" if its id was not in the previous list
        was_in = (new_i[:, :, None] == ids[:, None, :]).any(-1)
        new_flag = (~was_in) & (new_i != INVALID)
        n_updates = new_flag.sum()
    return new_i, new_d, new_flag, n_updates


@functools.partial(jax.jit, static_argnames=("cfg", "metric"), donate_argnums=(1, 2, 3))
def _round_jit(base, ids, dists, isnew, key, cfg, metric):
    return _round(base, ids, dists, isnew, key, cfg, metric)


@functools.partial(jax.jit, static_argnames=("cfg", "metric"))
@jax.named_scope("nd_init")
def _init_jit(base, key, cfg, metric):
    """Random k-lists, scored and deduped: the state round 0 starts from."""
    ids = _random_init(key, base.shape[0], cfg.k)
    dists = _score_chunked(base, ids, metric, cfg.chunk)
    dists, ids = jax.vmap(dedup_by_id)(dists, ids)
    return ids, dists, jnp.ones_like(ids, dtype=bool)


class NNDescentStats(NamedTuple):
    """Convergence provenance of one NN-Descent run (BuildReport currency).

    rounds       : rounds actually executed (<= cfg.rounds when the
                   early-termination rule fired)
    update_curve : per-round new-entry counts — the standard NN-Descent
                   convergence diagnostic (monotone-ish decay to ~0)
    converged    : True iff the delta * n * K early-termination threshold
                   fired before the round budget ran out
    threshold    : the realized update-count threshold (delta * n * K)
    """

    rounds: int
    update_curve: tuple[int, ...]
    converged: bool
    threshold: float


def build_knn_graph_with_stats(
    base: jax.Array,
    cfg: NNDescentConfig = NNDescentConfig(),
    metric: str = "l2",
    key: jax.Array | None = None,
    verbose: bool = False,
) -> tuple[KnnGraph, NNDescentStats]:
    """Run NN-Descent to convergence; returns the KGraph-style k-NN graph
    plus its convergence stats (same loop as :func:`build_knn_graph` — the
    graph is bit-identical for equal inputs)."""
    if key is None:
        key = jax.random.PRNGKey(0)
    n = base.shape[0]
    k0, key = jax.random.split(key)
    ids, dists, isnew = _init_jit(base, k0, cfg, metric)

    threshold = cfg.delta * n * cfg.k
    curve: list[int] = []
    converged = False
    for r in range(cfg.rounds):
        t0 = time.perf_counter()
        key, kr = jax.random.split(key)
        with jax.profiler.TraceAnnotation("ann.nd_round", round=r):
            ids, dists, isnew, n_up = _round_jit(base, ids, dists, isnew, kr,
                                                 cfg, metric)
            n_up = int(n_up)
        curve.append(n_up)
        if verbose:
            print(f"[nndescent] round {r}: {n_up} updates, "
                  f"{time.perf_counter() - t0:.3f} s", flush=True)
        if n_up <= threshold:
            converged = True
            break
    stats = NNDescentStats(rounds=len(curve), update_curve=tuple(curve),
                           converged=converged, threshold=threshold)
    return KnnGraph(neighbors=ids, dists=dists), stats


def build_knn_graph(
    base: jax.Array,
    cfg: NNDescentConfig = NNDescentConfig(),
    metric: str = "l2",
    key: jax.Array | None = None,
    verbose: bool = False,
) -> KnnGraph:
    """Run NN-Descent to convergence; returns the KGraph-style k-NN graph."""
    graph, _ = build_knn_graph_with_stats(base, cfg, metric=metric, key=key,
                                          verbose=verbose)
    return graph


def graph_recall(graph: KnnGraph, exact: KnnGraph) -> float:
    """Fraction of true k-NN edges recovered (the KGraph quality metric)."""
    hit = (graph.neighbors[:, :, None] == exact.neighbors[:, None, :]) & (
        exact.neighbors[:, None, :] != INVALID
    )
    return float(hit.any(1).mean())
