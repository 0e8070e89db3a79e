"""Batched best-first ("hill-climbing" / ef-) search over a flat graph.

This is the search procedure every graph method in the paper shares
(Sec. III): maintain a sorted ef-candidate list; repeatedly expand the best
unexpanded vertex; stop when the best unexpanded candidate is farther than
the worst list entry.

TPU-native batching (DESIGN.md §2): Q queries advance in lock-step inside one
``lax.while_loop``; per step each query expands one vertex, the (Q, R)
neighbor gather + scoring is a single fused kernel call, and the per-query
visited set is a bit-packed (Q, ceil(n/32)) uint32 matrix. Finished queries
are masked, not exited (SIMT-style divergence handling).

``search_with_trace`` runs a fixed-step scan recording (min distance reached,
cumulative comparisons) — the instrumentation behind paper Fig. 6.

Every phase runs under a ``jax.named_scope`` (``beam_init``, ``beam_select``,
``beam_expand``, ``beam_score``, ``beam_visited``, ``beam_merge``,
``beam_freeze``, ``beam_finalize``), which names it in the HLO ``op_name``
metadata and so in a device trace; the scopes change no computation. The
scorer's test of the visited bitmap carries the kernels' own
``visited_test`` scope, inside ``beam_score`` (``beam_init`` for the seeds).

Termination is per query (DESIGN.md §12). ``term="fixed"`` keeps the classic
rule only: a row stops when its best unexpanded candidate cannot improve the
ef list. ``term="stable"`` additionally freezes a row whose top-k has not
improved for ``stable_steps`` consecutive steps — the same ``done`` masking
``q_valid`` padding uses, so a frozen row's neighbor slots are INVALID in the
fused gather and it accrues zero comparisons from the freeze on.
``restarts > 0`` resurrects converged rows GNNS-style with fresh per-row-keyed
seeds (scored through the scorer, charged to ``n_comps``), bounded by the
budget; draws fold each row's own key, never the batch shape, so padded or
bucketed batches restart bit-identically to direct searches.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .scorers import get_scorer
from .topk import INVALID, topk_smallest, topk_smallest_with

INF = jnp.float32(jnp.inf)


class SearchResult(NamedTuple):
    ids: jax.Array        # (Q, k) ascending
    dists: jax.Array      # (Q, k)
    n_comps: jax.Array    # (Q,) distance computations (paper's cost currency)
    n_steps: jax.Array    # () loop iterations executed
    # bytes of base representation fetched per query (DESIGN.md §15): the
    # scorer's scored bytes (4d exact / d sq8 / M pq per vertex) plus the
    # rerank tail's row fetches, billed at the backing tier's granularity
    # (row_bytes on device/host, whole deduplicated 4 KiB pages on disk) —
    # the ladder's memory-traffic currency, comparable across placements
    bytes_touched: jax.Array | int = 0

    @property
    def host_bytes(self):
        """Pre-§15 name for :attr:`bytes_touched` (tier traffic was billed
        only for the host placement then); kept for older callers."""
        return self.bytes_touched


class TraverseResult(NamedTuple):
    """A finished traversal before the rerank tail: the full candidate list
    in the scorer's own currency. ``beam_traverse`` returns this so the
    tiered-base path (``core.base_store``) can gather the survivor rows from
    host memory OUTSIDE the jitted loop and finish the exact rerank there."""

    cand_ids: jax.Array    # (Q, ef) ascending by scorer distance
    cand_dists: jax.Array  # (Q, ef) scorer currency (ADC under pq)
    n_comps: jax.Array     # (Q,) raw scored-id count (unscaled)
    n_steps: jax.Array     # ()


class _State(NamedTuple):
    cand_ids: jax.Array    # (Q, ef) sorted ascending by dist
    cand_dists: jax.Array  # (Q, ef)
    expanded: jax.Array    # (Q, ef) bool
    visited: jax.Array     # (Q, W) uint32 bitmap
    n_comps: jax.Array     # (Q,)
    done: jax.Array        # (Q,)
    step: jax.Array        # ()
    stale: jax.Array       # (Q,) consecutive steps without top-k improvement
    restarts_used: jax.Array  # (Q,) fresh-seed restarts spent so far
    seed_best: jax.Array   # (Q,) best seed-phase distance (restart gate ref)


TERMINATION_MODES = ("fixed", "stable")


def check_termination(term: str, restarts: int, restart_keys) -> None:
    """Shared validation for the adaptive-termination knobs — every beam
    entry point fails loudly, pre-trace, on an unknown mode or an unkeyed
    restart request."""
    if term not in TERMINATION_MODES:
        raise ValueError(
            f"unknown termination mode {term!r}; one of {TERMINATION_MODES}"
        )
    if restarts > 0 and restart_keys is None:
        raise ValueError(
            "restarts > 0 needs restart_keys: (Q, 2) uint32, one PRNG key "
            "per row (Searcher derives them as fold_in(key, row)). Restart "
            "draws are keyed per row, never per batch shape, so "
            "padded/bucketed serving stays bit-identical to direct search."
        )


def default_max_steps(ef: int, expand_width: int = 1) -> int:
    """Step budget: the beam converges in O(ef) expansions, and expand_width
    W expands W vertices per step, so W-wide runs finish in ~1/W the steps —
    a fixed 4*ef + 64 would make wide fixed-step scans burn W-fold dead work."""
    return -(-4 * ef // expand_width) + 64


def mask_padded_queries(entry_ids: jax.Array,
                        q_valid: jax.Array | None) -> jax.Array:
    """Padding-row seeding policy (DESIGN.md §11): rows with ``q_valid``
    False get an all-INVALID entry row, so they score zero comparisons at
    init, freeze on the first step (nothing expandable), and return
    (INVALID, +inf, 0 comps). Every per-row statistic of a real row is
    bit-identical to the unpadded search — the invariant bucketed serving
    pads on. ``q_valid=None`` means all rows are real."""
    if q_valid is None:
        return entry_ids
    return jnp.where(q_valid[:, None], entry_ids, INVALID)


def dedup_rows(ids: jax.Array) -> jax.Array:
    """Sort each row and mark repeats INVALID — the dup-free-rows invariant
    ``_mark_visited``'s scatter-add requires. Order is not preserved."""
    srt = jnp.sort(ids, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((ids.shape[0], 1), bool), srt[:, 1:] == srt[:, :-1]],
        axis=1,
    )
    return jnp.where(dup, INVALID, srt)


def _is_visited(visited: jax.Array, ids: jax.Array) -> jax.Array:
    """Read bits for ids (Q, R) from the bit-packed bitmap; ids < 0 read
    False (padding is never 'visited' — it is dropped by validity masks)."""
    Q, W = visited.shape
    safe = jnp.maximum(ids, 0)
    q = jnp.broadcast_to(jnp.arange(Q)[:, None], ids.shape)
    words = visited[q, jnp.minimum(safe >> 5, W - 1)]
    seen = (words >> (safe & 31).astype(jnp.uint32)) & 1 > 0
    return seen & (ids >= 0)


def _mark_visited(visited: jax.Array, ids: jax.Array) -> jax.Array:
    """Set bits for ids (Q, R); ids < 0 are ignored. Rows must be dup-free
    among unvisited entries (guaranteed: adjacency rows are deduped)."""
    Q, W = visited.shape
    valid = ids >= 0
    word = jnp.where(valid, ids >> 5, W)           # sentinel word dropped
    bit = jnp.where(valid, jnp.uint32(1) << (ids & 31).astype(jnp.uint32), 0)
    q = jnp.broadcast_to(jnp.arange(Q)[:, None], ids.shape)
    return visited.at[q, word].add(bit, mode="drop")


@jax.named_scope("beam_init")
def _init_state(queries, base, neighbors, entry_ids, ef, metric,
                r_tile: int = 0, scorer: str = "exact",
                scorer_state=None, tombstones=None, deny=None) -> _State:
    Q = queries.shape[0]
    # n comes from the adjacency, not the base: under base_placement='host'
    # the traversal runs with base=None (the float rows never reach the
    # device; the scorer reads the code table from scorer_state instead).
    n = neighbors.shape[0]
    W = (n + 31) // 32
    E = entry_ids.shape[1]

    # Deleted/unallocated ids (tombstones, (W,)) and filter-denied ids (deny,
    # (W,) shared or (Q, W) per-query — DESIGN.md §14) arrive as packed
    # bitmaps and OR into every row's INITIAL visited set: the fused mask
    # epilogue then returns (+inf, INVALID) for them at seeding, every hop,
    # and every restart draw — exclusion sets ride the existing visited
    # plumbing with zero kernel changes and zero recompiles (the bitmaps are
    # operands, not static args, so new tombstone/filter VALUES reuse the
    # compiled executable).
    if tombstones is None and deny is None:
        init = jnp.zeros((Q, W), jnp.uint32)
    else:
        init = jnp.zeros((W,), jnp.uint32)
        if tombstones is not None:
            init = init | tombstones.astype(jnp.uint32)
        init = init[None, :]
        if deny is not None:
            d = deny.astype(jnp.uint32)
            init = init | (d if d.ndim == 2 else d[None, :])
        init = jnp.broadcast_to(init, (Q, W))

    # seeds are scored in the scorer's own currency (ADC scores under pq):
    # the candidate list must stay comparable across the whole traversal.
    # A zero bitmap makes the masked call a plain scored gather; tombstone
    # bits knock dead seeds out before they cost a comparison.
    d0, entry_ids = get_scorer(scorer).score(
        scorer_state, queries, base, entry_ids,
        init, metric=metric, r_tile=r_tile,
    )  # (Q, E)
    visited = _mark_visited(init, entry_ids)

    pad = ef - E
    cand_d = jnp.concatenate([d0, jnp.full((Q, pad), INF)], axis=1)
    cand_i = jnp.concatenate(
        [entry_ids, jnp.full((Q, pad), INVALID, jnp.int32)], axis=1
    )
    cand_d, cand_i = topk_smallest_with(cand_d, (cand_i,), ef)
    return _State(
        cand_ids=cand_i,
        cand_dists=cand_d,
        expanded=jnp.zeros((Q, ef), bool),
        visited=visited,
        # entry rows may carry INVALID padding (e.g. deduped random seeds)
        n_comps=(entry_ids >= 0).sum(axis=1, dtype=jnp.int32),
        done=jnp.zeros((Q,), bool),
        step=jnp.int32(0),
        stale=jnp.zeros((Q,), jnp.int32),
        restarts_used=jnp.zeros((Q,), jnp.int32),
        seed_best=cand_d[:, 0],
    )


def _restart_rows(queries, base, metric, r_tile, scorer, scorer_state,
                  restart_keys, restarts: int, restart_gate: float,
                  n: int, E: int, seed_best,
                  cand_i, cand_d, cand_e, visited, n_comps, done, stale,
                  restarts_used):
    """GNNS-style restart (DESIGN.md §12): a row that converged with budget
    left — and, when ``restart_gate > 0``, whose best (scorer-currency, i.e.
    PQ/LSH-estimated under compressed scorers) distance is still worse than
    ``gate * its own seed-phase best`` — draws E fresh seeds from its OWN
    folded key, scores them through the scorer (charged to ``n_comps``),
    marks them visited, merges them unexpanded, and resumes. Rows not
    restarting pass through bit-unchanged (their draws are INVALID, scored
    to +inf, and the re-merge of an already-sorted list is the identity)."""
    can = done & (restarts_used < restarts) & (cand_d[:, 0] < INF)
    if restart_gate > 0.0:
        # per-row poor-answer gate: the walk barely improved on its seeds
        can = can & (cand_d[:, 0] > restart_gate * seed_best)
    folded = jax.vmap(jax.random.fold_in)(restart_keys, restarts_used)
    draws = jax.vmap(
        lambda kk: jax.random.randint(kk, (E,), 0, n, dtype=jnp.int32)
    )(folded)
    draws = dedup_rows(jnp.where(can[:, None], draws, INVALID))
    rd, rids = get_scorer(scorer).score(
        scorer_state, queries, base, draws, visited,
        metric=metric, r_tile=r_tile,
    )                                                                # (Q, E)
    n_comps = n_comps + (rids >= 0).sum(axis=1, dtype=jnp.int32)
    visited = _mark_visited(visited, rids)
    Q, ef = cand_i.shape
    all_d = jnp.concatenate([cand_d, rd], axis=1)
    all_i = jnp.concatenate([cand_i, rids], axis=1)
    all_e = jnp.concatenate([cand_e, jnp.zeros((Q, E), bool)], axis=1)
    cand_d, cand_i, cand_e = topk_smallest_with(all_d, (all_i, all_e), ef)
    return (cand_i, cand_d, cand_e, visited, n_comps,
            done & ~can, jnp.where(can, 0, stale),
            restarts_used + can.astype(jnp.int32))


def _step(state: _State, queries, base, neighbors, metric,
          expand_width: int = 1, r_tile: int = 0, scorer: str = "exact",
          scorer_state=None, k: int = 1, term: str = "fixed",
          stable_steps: int = 8, restarts: int = 0,
          restart_gate: float = 0.0, restart_keys=None) -> _State:
    Q, ef = state.cand_ids.shape
    R = neighbors.shape[1]

    # 1. best unexpanded candidate(s) per query. expand_width > 1 is the
    # beyond-paper variant: W vertices expand per step, trading a few extra
    # comparisons for W-fold fewer sequential steps (bigger fused gathers on
    # the MXU, W-fold fewer device round-trips) — §Perf-ANN.
    with jax.named_scope("beam_select"):
        masked = jnp.where(state.expanded, INF, state.cand_dists)
        W = expand_width
        if W == 1:
            j = jnp.argmin(masked, axis=1)[:, None]                  # (Q, 1)
        else:
            _, j = jax.lax.top_k(-masked, W)                         # (Q, W)
        best_d = jnp.take_along_axis(masked, j, axis=1)              # (Q, W)
        worst = state.cand_dists[:, -1]
        # termination: nothing expandable, or best unexpanded worse than the
        # full list's worst (cannot improve the ef set)
        newly_done = (best_d[:, 0] == INF) | (best_d[:, 0] > worst)
        done = state.done | newly_done
        active = ~done

        vtx = jnp.take_along_axis(state.cand_ids, j, axis=1)         # (Q, W)
        expandable = (best_d < INF) & active[:, None]
        expanded = state.expanded.at[
            jnp.broadcast_to(jnp.arange(Q)[:, None], j.shape), j
        ].max(expandable)

    # 2. gather neighbors; mask padding/inactive
    with jax.named_scope("beam_expand"):
        nbrs = neighbors[jnp.maximum(vtx, 0)].reshape(Q, W * R)      # (Q, W*R)
        nbrs = jnp.where((nbrs >= 0) & jnp.repeat(expandable, R, axis=1),
                         nbrs, INVALID)
        # dedup within the row (two expanded vertices may share a neighbor)
        if W > 1:
            nbrs = dedup_rows(nbrs)

    # 3. score + mask + account + mark visited, through the scorer axis
    # (DESIGN.md §8). The visited-bitmap test and the validity mask are fused
    # into the kernel epilogue either way: the kernel returns (+inf, INVALID)
    # for padding/visited entries directly.
    with jax.named_scope("beam_score"):
        nd, nbrs = get_scorer(scorer).score(
            scorer_state, queries, base, nbrs, state.visited,
            metric=metric, r_tile=r_tile,
        )                                                            # (Q, W*R)
        n_comps = state.n_comps + (nbrs >= 0).sum(axis=1, dtype=jnp.int32)
    with jax.named_scope("beam_visited"):
        visited = _mark_visited(state.visited, nbrs)

    # 4. merge (no dedup needed: visited-filtering guarantees uniqueness).
    # The ef best of the (ef + W*R) merged candidates, ids and expanded flags
    # carried through one stable sort (DESIGN.md §2 rule 4): on the TPU top_k
    # is that same full-width sort, while reordering ids and flags by its
    # index would take a gather each. Ties keep the lowest index first.
    with jax.named_scope("beam_merge"):
        all_d = jnp.concatenate([state.cand_dists, nd], axis=1)
        all_i = jnp.concatenate([state.cand_ids, nbrs], axis=1)
        all_e = jnp.concatenate(
            [expanded, jnp.zeros((Q, nbrs.shape[1]), bool)], axis=1
        )
        cand_d, cand_i, cand_e = topk_smallest_with(all_d, (all_i, all_e), ef)

        # frozen queries keep their state
        keep = lambda new, old: jnp.where(done[:, None], old, new)
        cand_i = keep(cand_i, state.cand_ids)
        cand_d = keep(cand_d, state.cand_dists)
        cand_e = keep(cand_e, state.expanded)
        n_comps = jnp.where(done, state.n_comps, n_comps)
    with jax.named_scope("beam_visited"):
        visited = jnp.where(done[:, None], state.visited, visited)

    # per-query stability freeze (term="stable", DESIGN.md §12): a row whose
    # top-k has not strictly improved for stable_steps consecutive steps is
    # done — next step its expandable mask is False, so it stops paying
    # comparisons exactly like a q_valid padding row. Static branch: the
    # fixed mode traces none of this and stays bit-identical to the classic
    # rule above.
    stale = state.stale
    restarts_used = state.restarts_used
    with jax.named_scope("beam_freeze"):
        if term == "stable":
            kk = min(k, ef)
            improved = (cand_d[:, :kk] < state.cand_dists[:, :kk]).any(axis=1)
            stale = jnp.where(done, state.stale,
                              jnp.where(improved, 0, state.stale + 1))
            done = done | (stale >= stable_steps)
        if restarts > 0:
            (cand_i, cand_d, cand_e, visited, n_comps, done, stale,
             restarts_used) = _restart_rows(
                queries, base, metric, r_tile, scorer, scorer_state,
                restart_keys, restarts, restart_gate,
                neighbors.shape[0], min(ef, 8), state.seed_best,
                cand_i, cand_d, cand_e, visited, n_comps, done, stale,
                restarts_used,
            )
    return _State(
        cand_ids=cand_i,
        cand_dists=cand_d,
        expanded=cand_e,
        visited=visited,
        n_comps=n_comps,
        done=done,
        step=state.step + 1,
        stale=stale,
        restarts_used=restarts_used,
        seed_best=state.seed_best,
    )


@jax.named_scope("beam_finalize")
def _finalize(state: _State, queries, base, k, metric, r_tile,
              scorer: str, scorer_state, rerank: int) -> SearchResult:
    """Loop epilogue. Exact scorer: slice the candidate list. Compressed
    scorers: exact-rerank the top ``rerank`` survivors (0 = all ef) and
    convert the scored-id count into the paper's comparison currency —
    M/d per ADC score plus one full comparison per reranked candidate."""
    sc = get_scorer(scorer)
    d_dim = base.shape[1]
    if not sc.needs_rerank:
        return SearchResult(
            ids=state.cand_ids[:, :k],
            dists=state.cand_dists[:, :k],
            n_comps=state.n_comps,
            n_steps=state.step,
            bytes_touched=sc.scored_bytes(scorer_state, state.n_comps, d_dim),
        )
    from repro.kernels import ops

    ef = state.cand_ids.shape[1]
    r = rerank_slice(ef, k, rerank)
    cand = state.cand_ids[:, :r]                # ascending by ADC score
    exact = ops.gather_distance(queries, cand, base, metric=metric,
                                r_tile=r_tile)  # INVALID -> +inf
    dd, sel = topk_smallest(exact, k)
    n_comps = sc.scale_comps(scorer_state, state.n_comps, base.shape[1])
    n_cand = (cand >= 0).sum(axis=1, dtype=jnp.int32)
    # scored codes during traversal + float rows the in-HBM rerank gathered
    # (the tiered path replaces the row term with the store's own billing)
    bytes_touched = (sc.scored_bytes(scorer_state, state.n_comps, d_dim)
                     + n_cand * (4 * d_dim))
    return SearchResult(
        ids=jnp.take_along_axis(cand, sel, axis=1),
        dists=dd,
        n_comps=n_comps + n_cand,
        n_steps=state.step,
        bytes_touched=bytes_touched,
    )


@functools.partial(
    jax.jit,
    static_argnames=("ef", "k", "metric", "max_steps", "expand_width",
                     "r_tile", "scorer", "rerank", "term", "stable_steps",
                     "restarts", "restart_gate"),
)
def beam_search(
    queries: jax.Array,
    base: jax.Array,
    neighbors: jax.Array,
    entry_ids: jax.Array,
    ef: int,
    k: int = 1,
    metric: str = "l2",
    max_steps: int | None = None,
    expand_width: int = 1,
    r_tile: int = 0,
    scorer: str = "exact",
    scorer_state=None,
    rerank: int = 0,
    q_valid: jax.Array | None = None,
    term: str = "fixed",
    stable_steps: int = 8,
    restarts: int = 0,
    restart_gate: float = 0.0,
    restart_keys: jax.Array | None = None,
    tombstones: jax.Array | None = None,
    deny: jax.Array | None = None,
) -> SearchResult:
    """Best-first graph search. entry_ids (Q, E) seeds (E <= ef).
    expand_width > 1 expands several vertices per step (beyond-paper);
    r_tile sets the gather kernel's neighbor tile (0 = kernel default);
    scorer picks the per-hop distance implementation (``core.scorers``) with
    ``scorer_state`` its per-batch operand pytree, and compressed scorers
    finish with an exact rerank of the ``rerank`` best survivors (0 = ef);
    q_valid (Q,) bool marks real rows — padding rows (False) cost zero
    comparisons and return (INVALID, +inf), see ``mask_padded_queries``;
    term="stable" freezes rows whose top-k stalls for ``stable_steps`` steps,
    and ``restarts``/``restart_gate``/``restart_keys`` resurrect converged
    rows from fresh per-row-keyed seeds (module docstring / DESIGN.md §12);
    tombstones (ceil(n/32),) packed uint32 marks deleted/unallocated ids —
    they seed every row's visited bitmap, so dead vertices score +inf
    everywhere and cost zero comparisons (DESIGN.md §13); deny is the same
    mechanism for filter/namespace predicates (DESIGN.md §14) — (W,) shared
    across the batch or (Q, W) per query, ORed with the tombstones into the
    initial visited set, so denied ids are never scored, never expanded,
    never returned, at zero extra kernel cost and zero recompiles across
    filter values."""
    check_termination(term, restarts, restart_keys)
    if max_steps is None:
        max_steps = default_max_steps(ef, expand_width)
    entry_ids = mask_padded_queries(entry_ids, q_valid)
    state = _init_state(queries, base, neighbors, entry_ids, ef, metric,
                        r_tile, scorer, scorer_state, tombstones, deny)

    def cond(s: _State):
        return (~s.done.all()) & (s.step < max_steps)

    def body(s: _State):
        return _step(s, queries, base, neighbors, metric, expand_width,
                     r_tile, scorer, scorer_state, k, term, stable_steps,
                     restarts, restart_gate, restart_keys)

    state = jax.lax.while_loop(cond, body, state)
    return _finalize(state, queries, base, k, metric, r_tile, scorer,
                     scorer_state, rerank)


@functools.partial(
    jax.jit,
    static_argnames=("ef", "metric", "max_steps", "expand_width", "r_tile",
                     "scorer", "k", "term", "stable_steps", "restarts",
                     "restart_gate"),
)
def beam_traverse(
    queries: jax.Array,
    neighbors: jax.Array,
    entry_ids: jax.Array,
    ef: int,
    metric: str = "l2",
    max_steps: int | None = None,
    expand_width: int = 1,
    r_tile: int = 0,
    scorer: str = "pq",
    scorer_state=None,
    q_valid: jax.Array | None = None,
    k: int = 1,
    term: str = "fixed",
    stable_steps: int = 8,
    restarts: int = 0,
    restart_gate: float = 0.0,
    restart_keys: jax.Array | None = None,
    tombstones: jax.Array | None = None,
    deny: jax.Array | None = None,
) -> TraverseResult:
    """The beam loop WITHOUT the rerank tail — the device half of a tiered
    search (DESIGN.md §9). No ``base`` operand: the scorer must be base-free
    (``needs_base=False``, i.e. it scores hops off device-resident state such
    as the PQ code table), so the only device-resident per-index arrays are
    that state and ``neighbors``. The caller finishes with an exact rerank of
    ``cand_ids`` against wherever the float rows live (``BaseStore.gather``).
    Numerics are identical to ``beam_search``'s loop — same ``_init_state`` /
    ``_step`` bodies, same operands (``k`` here only sizes the term="stable"
    stability window; the full ef list is returned either way). ``deny``
    (filter bitmap, §14) composes with ``tombstones`` by OR exactly as in
    ``beam_search`` — the candidate list the host rerank receives already
    contains only allowed ids."""
    sc = get_scorer(scorer)
    if getattr(sc, "needs_base", True):
        raise ValueError(
            f"beam_traverse needs a base-free scorer (got {scorer!r}): the "
            "float base is not an operand here — use beam_search, or a "
            "base-free scorer ('pq', 'sq8')"
        )
    check_termination(term, restarts, restart_keys)
    if max_steps is None:
        max_steps = default_max_steps(ef, expand_width)
    entry_ids = mask_padded_queries(entry_ids, q_valid)
    state = _init_state(queries, None, neighbors, entry_ids, ef, metric,
                        r_tile, scorer, scorer_state, tombstones, deny)

    def cond(s: _State):
        return (~s.done.all()) & (s.step < max_steps)

    def body(s: _State):
        return _step(s, queries, None, neighbors, metric, expand_width,
                     r_tile, scorer, scorer_state, k, term, stable_steps,
                     restarts, restart_gate, restart_keys)

    state = jax.lax.while_loop(cond, body, state)
    return TraverseResult(
        cand_ids=state.cand_ids,
        cand_dists=state.cand_dists,
        n_comps=state.n_comps,
        n_steps=state.step,
    )


def rerank_slice(ef: int, k: int, rerank: int) -> int:
    """How many ADC survivors the exact rerank touches — ``_finalize``'s
    policy (0 = the whole ef list), shared with the tiered host rerank so
    both placements rerank the SAME survivor set."""
    return ef if rerank <= 0 else max(k, min(rerank, ef))


@functools.partial(
    jax.jit,
    static_argnames=("ef", "k", "metric", "max_steps", "expand_width",
                     "r_tile", "scorer", "rerank", "term", "stable_steps",
                     "restarts", "restart_gate"),
)
def search_with_trace(
    queries: jax.Array,
    base: jax.Array,
    neighbors: jax.Array,
    entry_ids: jax.Array,
    ef: int,
    k: int = 1,
    metric: str = "l2",
    max_steps: int | None = None,
    expand_width: int = 1,
    r_tile: int = 0,
    scorer: str = "exact",
    scorer_state=None,
    rerank: int = 0,
    term: str = "fixed",
    stable_steps: int = 8,
    restarts: int = 0,
    restart_gate: float = 0.0,
    restart_keys: jax.Array | None = None,
    tombstones: jax.Array | None = None,
    deny: jax.Array | None = None,
) -> tuple[SearchResult, jax.Array, jax.Array]:
    """Fixed-step variant recording the Fig. 6 statistics.

    ``max_steps`` defaults to :func:`default_max_steps`, which scales down
    with ``expand_width`` — the scan burns every step regardless of
    convergence, so a W-agnostic bound would waste W-fold work.

    Returns (result, trace_dist (steps, Q), trace_comps (steps, Q)) where
    trace_dist[t, q] is the best distance reached after step t and
    trace_comps[t, q] the cumulative distance computations. Under a
    compressed scorer the trace is in the scorer's own currency (ADC scores
    and raw scored-id counts); only the final result is reranked/rescaled.
    Adaptive termination traces too: after a term="stable" freeze a row's
    cumulative comparisons are constant for the rest of the scan — the
    property the frozen-rows-stop-paying test pins.
    """
    check_termination(term, restarts, restart_keys)
    if max_steps is None:
        max_steps = default_max_steps(ef, expand_width)
    state = _init_state(queries, base, neighbors, entry_ids, ef, metric,
                        r_tile, scorer, scorer_state, tombstones, deny)

    def body(s: _State, _):
        s2 = _step(s, queries, base, neighbors, metric, expand_width, r_tile,
                   scorer, scorer_state, k, term, stable_steps, restarts,
                   restart_gate, restart_keys)
        return s2, (s2.cand_dists[:, 0], s2.n_comps)

    state, (td, tc) = jax.lax.scan(body, state, None, length=max_steps)
    res = _finalize(state, queries, base, k, metric, r_tile, scorer,
                    scorer_state, rerank)
    return res, td, tc


def projection_entries(
    queries: jax.Array,
    base_proj: jax.Array,   # (n, m) projected base (m ~ 8, SRS-style)
    proj: jax.Array,        # (d, m)
    E: int,
) -> jax.Array:
    """Beyond-paper seed selection: instead of random seeds (flat-HNSW) or a
    hierarchy (HNSW), pick the E nearest candidates in a tiny m-dim random
    projection — an O(n*m) scan (m/d of one full pass) that recovers the
    hierarchy's early-phase savings (paper Fig. 6) with a flat graph."""
    qp = queries @ proj                                   # (Q, m)
    d = (
        jnp.sum(qp * qp, 1)[:, None]
        - 2.0 * qp @ base_proj.T
        + jnp.sum(base_proj * base_proj, 1)[None, :]
    )
    _, ids = jax.lax.top_k(-d, E)
    return ids.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n", "Q", "E"))
def random_entries(key: jax.Array, n: int, Q: int, E: int) -> jax.Array:
    """E random seeds per query (flat-HNSW start, paper Sec. IV).

    With-replacement draw + in-row dedup: O(Q*E log E) instead of the old
    per-query no-replacement permutation (O(Q*n), which dominated wall time
    for the ``random`` strategy — see ROADMAP). Collisions are marked INVALID
    rather than redrawn (the beam requires dup-free rows for its bit-packed
    visited scatter); at E << n they are rare and only shrink the seed set.
    """
    return dedup_rows(jax.random.randint(key, (Q, E), 0, n, dtype=jnp.int32))
