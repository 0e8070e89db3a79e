"""Unified search engine: (entry strategy x graph x beam core) — DESIGN.md §3.

The paper's Sec. IV finding — HNSW's hierarchy is not a complexity win, it is
merely one way to pick good entry points for the same flat best-first search —
is made architectural here: there is ONE beam core (``beam_search``), one flat
adjacency, and a registry of pluggable *entry strategies* that only decide
where the beam starts:

* ``random``     — E uniform seeds (the paper's flat-HNSW control),
* ``projection`` — E nearest in a tiny random projection (SRS-style scan),
* ``hierarchy``  — HNSW greedy descent reduced to a 1-seed picker
                   (operationalizing the paper's Sec. IV claim),
* ``lsh``        — projection probe + exact rerank (coarse-quantizer seeding
                   on top of ``baselines/lsh.py``'s SRS sketch),
* ``hubs``       — the top in-degree vertices of the realized graph, scored
                   exactly and the nearest taken (arXiv:2412.01940: the
                   hierarchy's real contribution is landing on hubs — this
                   seeder pays a ``hub_count``-point scan instead of a
                   multi-layer descent for the same landing zone).

``hnsw_search``, ``flat_search`` and ``distributed_search`` are thin wrappers
over this module; a new seeder, metric, or shard layout plugs in here once and
every caller (core, distributed, serve, benchmarks) picks it up.

Seed-phase distance computations are charged to ``SearchResult.n_comps`` in
the paper's cost currency: the hierarchy descent counts its greedy
comparisons, projection/lsh count the m-dim scan at m/d of a full comparison
per base point (the paper's accounting for SRS), plus any exact rerank.
"""
from __future__ import annotations

import functools
import zlib
from collections import OrderedDict
from typing import NamedTuple, Protocol

import jax
import jax.numpy as jnp

from .base_store import BaseStore, check_placement, rerank_gathered
from .beam_search import (
    SearchResult,
    TraverseResult,
    beam_search,
    beam_traverse,
    projection_entries,
    random_entries,
    rerank_slice,
    search_with_trace,
)
from .filters import CompiledFilter, FilterSpec, compile_filter, \
    remap_denied_seeds
from .graph_index import HnswIndex, KnnGraph
from .scorers import SCORERS, get_scorer, register_scorer  # noqa: F401
from .topk import INVALID, topk_smallest


class SearchSpec(NamedTuple):
    """Static search configuration (a pytree of hashable leaves).

    One spec drives every layer: single-host ``Searcher.search``, the
    per-shard body of ``distributed_search``, and the serving loop. The
    axes, by DESIGN.md section:

    * entry (§3, §12): ``entry`` / ``n_entries`` / ``proj_dim`` /
      ``lsh_probes`` / ``hub_count`` pick where the beam starts;
    * beam core (§2, §5): ``ef`` / ``k`` / ``expand_width`` / ``max_steps``
      / ``r_tile`` shape the one flat best-first walk;
    * scorer (§8): ``scorer`` / ``rerank`` / ``pq_*`` trade per-hop
      distance fidelity for memory;
    * placement (§9): ``base_placement`` decides which memory tier holds
      the float base;
    * termination (§12): ``term`` / ``stable_steps`` / ``restarts`` /
      ``restart_gate`` make stopping per-query;
    * filtering (§14): ``filter`` restricts answers to a metadata
      predicate / tenant namespace — an operand, never a recompile.
    """

    ef: int = 64                # candidate-list width of the beam core
    k: int = 1                  # answers returned per query
    metric: str = "l2"
    entry: str = "random"       # key into ENTRY_STRATEGIES
    n_entries: int = 8          # seeds handed to the beam (capped at ef)
    expand_width: int = 1       # vertices expanded per step (§Perf-ANN)
    max_steps: int | None = None
    proj_dim: int = 8           # sketch width for projection/lsh seeding
    lsh_probes: int = 64        # rerank candidates for the lsh seeder
    r_tile: int = 0             # gather-kernel neighbor tile (0 = default)
    scorer: str = "exact"       # key into SCORERS (per-hop distance impl)
    rerank: int = 0             # exact-reranked survivors under compressed
                                # scorers (0 = all ef); ignored for exact
    pq_m: int = 8               # PQ sub-vectors (bytes/vector of the codes)
    pq_k: int = 256             # PQ codewords per sub-quantizer
    pq_iters: int = 15          # k-means iterations at PQ train time
    base_placement: str = "device"  # where the float base lives (§9, §15):
                                # "device" = HBM-resident (status quo);
                                # "host" = host-resident, device keeps only
                                # codes + adjacency, rerank gathers from host;
                                # "disk" = mmap'd row shards, rerank reads
                                # only the survivors' pages
    store_dtype: str = "f32"    # rerank-tier residual width (§15): "f32"
                                # keeps host/disk bit-identical to device;
                                # "bf16" halves tier bandwidth + footprint
                                # (device placement ignores this — the beam
                                # reranks the f32 base in-HBM)
    hub_count: int = 32         # hubs scanned per query by the hubs seeder
    term: str = "fixed"         # beam termination (§12): "fixed" = classic
                                # rule only; "stable" adds the per-query
                                # top-k stability freeze
    stable_steps: int = 8       # freeze after this many non-improving steps
    restarts: int = 0           # fresh-seed restarts per converged row
                                # (GNNS-style, comps-charged; 0 = off)
    restart_gate: float = 0.0   # restart only rows whose best distance is
                                # still > gate * their seed-phase best
                                # (0 = unconditional up to the budget)
    filter: FilterSpec | None = None  # metadata predicate / tenant
                                # namespace (§14): compiled once per
                                # (filter, index) into a packed deny bitmap
                                # that rides the mask epilogue; None = serve
                                # the whole index

    @property
    def num_seeds(self) -> int:
        return min(self.n_entries, self.ef)


class _HostPending(NamedTuple):
    """An in-flight host-tier search: traversal done, survivor rows on their
    way from host memory (async ``device_put``). ``Searcher._host_finish``
    turns it into a :class:`SearchResult`; holding several of these is how
    ``search_stream`` pipelines copies against compute."""

    spec: SearchSpec
    queries: jax.Array
    trav: TraverseResult
    cand: jax.Array        # (Q, r) survivor slice the rerank scores
    rows: jax.Array        # (Q, r, d) gathered float rows (possibly in flight)
    tier_bytes: jax.Array  # (Q,) rerank-tier traffic this query paid
    scorer_state: object
    entry_comps: jax.Array | None
    d: int


class EntryStrategy(Protocol):
    """Pluggable seed picker. ``prepare`` builds whatever per-index state the
    strategy needs (projection matrices, the layered index, ...); ``seed``
    maps a query batch to ((Q, E) entry ids, (Q,) seed-phase comparisons)."""

    name: str

    def prepare(self, base, neighbors, hierarchy, spec: SearchSpec, key): ...

    def seed(self, aux, queries, base, spec: SearchSpec, key): ...


ENTRY_STRATEGIES: dict[str, EntryStrategy] = {}


def get_entry_strategy(name: str) -> EntryStrategy:
    if name not in ENTRY_STRATEGIES:
        raise ValueError(
            f"unknown entry strategy {name!r}; registered: "
            f"{sorted(ENTRY_STRATEGIES)}"
        )
    return ENTRY_STRATEGIES[name]


def register_entry_strategy(strategy) -> EntryStrategy:
    """Register a seeder under ``strategy.name`` (the engine's one extension
    point — new seeding schemes never touch the beam core or its callers).
    Accepts a class (instantiated with no args) or a ready instance."""
    inst = strategy() if isinstance(strategy, type) else strategy
    ENTRY_STRATEGIES[inst.name] = inst
    return strategy


@register_entry_strategy
class _RandomEntry:
    name = "random"

    def prepare(self, base, neighbors, hierarchy, spec, key):
        return base.shape[0]

    def seed(self, aux, queries, base, spec, key):
        Q = queries.shape[0]
        ent = random_entries(key, aux, Q, spec.num_seeds)
        return ent, jnp.zeros((Q,), jnp.int32)


@register_entry_strategy
class _ProjectionEntry:
    name = "projection"

    def prepare(self, base, neighbors, hierarchy, spec, key):
        from repro.baselines.lsh import build_srs

        return build_srs(base, m=spec.proj_dim, key=key)

    def seed(self, aux, queries, base, spec, key):
        ent = projection_entries(queries, aux.base_proj, aux.proj,
                                 spec.num_seeds)
        n, m = aux.base_proj.shape
        scan = int(n * m / base.shape[1])  # m-dim pass at m/d of a comparison
        return ent, jnp.full((queries.shape[0],), scan, jnp.int32)


@register_entry_strategy
class _HierarchyEntry:
    name = "hierarchy"

    def prepare(self, base, neighbors, hierarchy, spec, key):
        if hierarchy is None:
            raise ValueError(
                "entry='hierarchy' needs a Searcher built from an HnswIndex"
            )
        return hierarchy

    def seed(self, aux, queries, base, spec, key):
        return hierarchy_entries(queries, base, aux, spec.metric)


@register_entry_strategy
class _LshEntry:
    name = "lsh"

    def prepare(self, base, neighbors, hierarchy, spec, key):
        from repro.baselines.lsh import build_srs

        return build_srs(base, m=spec.proj_dim, key=key)

    def seed(self, aux, queries, base, spec, key):
        # SRS probe + exact rerank, straight from the baseline. SRS is
        # l2-only (sketch and rerank); for other metrics the seeds are merely
        # suboptimal — the beam itself still scores with spec.metric.
        from repro.baselines.lsh import srs_search

        _, ids, comps = srs_search(
            queries, base, aux, k=spec.num_seeds, probes=spec.lsh_probes
        )
        return ids.astype(jnp.int32), comps


@register_entry_strategy
class _HubsEntry:
    name = "hubs"

    def prepare(self, base, neighbors, hierarchy, spec, key):
        # fallback for engines without an attached hub list (hand-assembled,
        # or rehydrated from a pre-v2 artifact): hubs are a deterministic
        # function of the adjacency, so this recompute is bit-identical to
        # what the build would have persisted.
        from .graph_index import hub_vertices

        return hub_vertices(neighbors, spec.hub_count)

    def prepare_ctx(self, searcher, spec, key):
        """Searcher-aware prepare: reuse the build-persisted hub list when it
        covers ``spec.hub_count`` (its prefix IS the top-``hub_count`` set —
        hubs are stored in-degree-descending)."""
        hubs = searcher.hubs
        if hubs is not None and hubs.shape[0] >= spec.hub_count:
            return jnp.asarray(hubs[: spec.hub_count])
        return self.prepare(searcher.base, searcher.neighbors,
                            searcher.hierarchy, spec, key)

    def seed(self, aux, queries, base, spec, key):
        # exact scan over the hub shortlist: H full comparisons buy a
        # query-dependent landing zone (what the hierarchy descent buys for
        # a comparable bill, without the layer structure)
        from repro.kernels import ops

        Q = queries.shape[0]
        H = aux.shape[0]
        ids = jnp.broadcast_to(aux[None, :], (Q, H))
        d = ops.gather_distance(queries, ids, base, metric=spec.metric,
                                r_tile=spec.r_tile)
        _, sel = topk_smallest(d, min(spec.num_seeds, H))
        ent = jnp.take_along_axis(ids, sel, axis=1)
        return ent.astype(jnp.int32), jnp.full((Q,), H, jnp.int32)


@functools.partial(jax.jit, static_argnames=("metric",))
def _greedy_layer(queries, base, nbrs_g, slot, start_ids, metric):
    """Greedy 1-NN descent on one layer (the coarse-to-fine step, Fig. 1).

    start_ids (Q,) -> (ids (Q,), dists (Q,), comps (Q,))."""
    from repro.kernels import ops

    Q = queries.shape[0]
    d0 = ops.gather_distance(queries, start_ids[:, None], base, metric=metric)[:, 0]

    def cond(s):
        _, _, _, done = s
        return ~done.all()

    def body(s):
        cur, cur_d, comps, done = s
        rows = nbrs_g[jnp.maximum(slot[jnp.maximum(cur, 0)], 0)]  # (Q, M)
        rows = jnp.where(done[:, None], INVALID, rows)
        nd = ops.gather_distance(queries, rows, base, metric=metric)
        comps = comps + (rows >= 0).sum(1, dtype=jnp.int32)
        j = jnp.argmin(nd, axis=1)
        best_d = jnp.take_along_axis(nd, j[:, None], 1)[:, 0]
        best_i = jnp.take_along_axis(rows, j[:, None], 1)[:, 0]
        better = best_d < cur_d
        return (
            jnp.where(better, best_i, cur),
            jnp.where(better, best_d, cur_d),
            comps,
            done | ~better,
        )

    cur, cur_d, comps, _ = jax.lax.while_loop(
        cond, body, (start_ids, d0, jnp.ones((Q,), jnp.int32), jnp.zeros((Q,), bool))
    )
    return cur, cur_d, comps


def hierarchy_entries(
    queries: jax.Array, base: jax.Array, index: HnswIndex, metric: str
) -> tuple[jax.Array, jax.Array]:
    """HNSW's upper layers as a seed picker: greedy descent from the top
    entry point down to layer 1, returning the (Q, 1) landing vertex and the
    comparisons spent — the paper's claim that the hierarchy is 'just' entry
    point selection, made literal."""
    Q = queries.shape[0]
    cur = jnp.full((Q,), index.entry_point, jnp.int32)
    comps = jnp.zeros((Q,), jnp.int32)
    for layer in range(index.num_layers - 1, 0, -1):
        cur, _, c = _greedy_layer(
            queries,
            base,
            index.layers_neighbors[layer],
            index.layers_slot[layer],
            cur,
            metric,
        )
        comps = comps + c
    return cur[:, None], comps


def filtered_brute_cutoff(spec: SearchSpec) -> int:
    """Allowed-set size at or below which a filtered search routes to the
    exact-scan fallback instead of the graph (DESIGN.md §14). Masking makes
    denied ids invisible but cannot make the allowed subgraph connected: once
    ``n_allowed`` is within a few multiples of ``ef``, the walk mostly scores
    denied neighbors for nothing while an exact scan over the allowed set is
    both cheaper and recall-1.0. Policy, not mechanism — callers that want a
    different threshold wrap :meth:`Searcher._filtered_brute` directly."""
    return max(4 * spec.ef, 192)


class Searcher:
    """(entry strategy x graph x beam core), bound to one dataset.

    Holds the base matrix, the flat adjacency the beam walks, and
    (optionally) an :class:`HnswIndex` whose upper layers back the
    ``hierarchy`` seeder. Also bound per index, all lazy/cached:

    * per-strategy prepared state (projections, sketches, hub shortlists),
      keyed by (strategy, sketch width, hub count);
    * PQ code tables for the ``pq`` scorer (attached from a build, or
      trained once per (M, K, iters));
    * a :class:`~repro.core.base_store.BaseStore` per ``base_placement``;
    * a packed tombstone bitmap (§13) marking deleted/unallocated rows —
      :class:`~repro.core.mutable.MutableIndex` swaps it as an operand;
    * metadata columns (dict of (n,) arrays: tenant ids, tags,
      timestamps) that ``SearchSpec.filter`` predicates read, with one
      :class:`~repro.core.filters.CompiledFilter` cached per spec (§14).
    """

    def __init__(self, base, neighbors, *, hierarchy: HnswIndex | None = None,
                 metric: str = "l2", key: jax.Array | None = None, pq=None,
                 hubs: jax.Array | None = None,
                 tombstones: jax.Array | None = None,
                 metadata: dict | None = None):
        self.base = base
        self.neighbors = neighbors
        self.hierarchy = hierarchy
        self.metric = metric
        self.key = key if key is not None else jax.random.PRNGKey(0)
        # top in-degree vertices backing the "hubs" seeder, in-degree
        # descending (attached from a build/artifact; None -> the strategy
        # recomputes from the adjacency on first use, bit-identically)
        self.hubs = hubs
        # (ceil(n/32),) packed uint32 marking deleted/unallocated row ids
        # (DESIGN.md §13): seeds every query's visited bitmap, so dead ids
        # read as INVALID in the fused mask epilogue at zero extra cost.
        # An operand, not a static arg — mutating it never recompiles.
        self.tombstones = tombstones
        # metadata columns for SearchSpec.filter predicates (DESIGN.md §14):
        # a dict of (n,) arrays ("tenant", "tag", "timestamp", ...). None is
        # fine until a filter that reads a column arrives.
        self.metadata = metadata
        # CompiledFilter LRU, keyed by FilterSpec (hashable): each LIVE
        # filter value is evaluated against the metadata once per index, and
        # the cache is bounded — a multi-tenant server cycling through
        # thousands of namespace filters no longer grows (n/8 + n/32)-byte
        # bitmap pairs without limit. Recency-evicted filters recompile on
        # return (filter_compiles counts compiles, for tests/ops).
        self._filters: OrderedDict[FilterSpec, CompiledFilter] = OrderedDict()
        self.filter_cache_size = 64
        self.filter_compiles = 0
        self._aux: dict[tuple, object] = {}
        # PQ code tables backing the "pq" scorer: ``pq`` is an externally
        # trained index attached at engine build time (served for any spec
        # matching its (M, K) — train iterations are its trainer's business);
        # lazily trained tables are cached per (M, K, iters).
        self._pq_attached = pq
        self._pq: dict[tuple, object] = {}
        # the sq8 scorer's scalar-quantized table (deterministic min/max
        # affine over the base — no PRNG, so no key-derivation parity to
        # keep; quantized once on first use)
        self._sq8 = None
        # provenance of the build that produced this index (set by
        # from_build; None for hand-assembled engines)
        self.build_report = None
        # BaseStore per (placement, dtype) (the "host" store is a one-time
        # host copy of the base, "disk" a one-time spill to mmap'd temp
        # shards; under a true n >> HBM deployment, construct the Searcher
        # from a host numpy base / an artifact's shards and the copy is free)
        self._stores: dict[tuple, BaseStore] = {}

    # -- constructors ---------------------------------------------------------

    @classmethod
    def from_graph(cls, base, graph: KnnGraph, **kw) -> "Searcher":
        return cls(base, graph.neighbors, **kw)

    @classmethod
    def from_hnsw(cls, base, index: HnswIndex, **kw) -> "Searcher":
        """Bottom layer becomes the flat graph; upper layers feed the
        ``hierarchy`` seeder. Every entry strategy then walks the SAME graph —
        the paper's controlled comparison."""
        return cls(base, index.layers_neighbors[0], hierarchy=index, **kw)

    @classmethod
    def from_build(cls, base, result, *, metric: str | None = None,
                   key: jax.Array | None = None) -> "Searcher":
        """Bind a :class:`~repro.core.build.BuildResult` to an engine: the
        flat graph feeds the beam, the hierarchy (if built) backs the
        ``hierarchy`` seeder, and a build-time PQ table is attached (the
        ``pq`` scorer then never trains at serve time). The report rides
        along as ``searcher.build_report`` and the build-time hub list backs
        the ``hubs`` seeder."""
        if metric is None:
            metric = result.report.spec.metric
        hubs = getattr(result, "hubs", None)
        if result.hierarchy is not None:
            searcher = cls.from_hnsw(base, result.hierarchy, metric=metric,
                                     key=key, pq=result.pq, hubs=hubs)
        else:
            searcher = cls.from_graph(base, result.graph, metric=metric,
                                      key=key, pq=result.pq, hubs=hubs)
        searcher.build_report = result.report
        return searcher

    @classmethod
    def build(cls, base, *, metric: str = "l2", key: jax.Array | None = None,
              graph_k: int = 20, with_hierarchy: bool = False,
              with_pq: bool = False, pq_m: int = 8, pq_k: int = 256,
              verbose: bool = False, spec=None) -> "Searcher":
        """Build the paper's hybrid index through the unified pipeline
        (``core.build``): construct · diversify · compress. Pass a
        :class:`~repro.core.build.BuildSpec` for full control; the legacy
        keyword surface maps onto it (``with_hierarchy`` -> the ``hnsw``
        constructor, default -> NN-Descent + GD, ``with_pq`` -> build-time
        PQ training). Bit-identical to the pre-pipeline builds for every
        configuration the old code could run (graph_k >= the NN-Descent
        sample width, 12); smaller graph_k used to crash in the local join
        and now works via the pipeline's sample clamp."""
        from .build import BuildSpec, GraphBuilder

        if spec is None:
            spec = BuildSpec(
                construct="hnsw" if with_hierarchy else "nndescent",
                diversify="none" if with_hierarchy else "gd",
                compress="pq" if with_pq else "none",
                metric=metric, graph_k=graph_k, pq_m=pq_m, pq_k=pq_k,
            )
        if key is None:
            key = jax.random.PRNGKey(0)
        result = GraphBuilder(spec).build(base, key=key, verbose=verbose)
        return cls.from_build(base, result, metric=spec.metric, key=key)

    # -- seeding --------------------------------------------------------------

    def spec(self, **kw) -> SearchSpec:
        """SearchSpec pre-filled with this searcher's metric."""
        kw.setdefault("metric", self.metric)
        return SearchSpec(**kw)

    def _check_metric(self, spec: SearchSpec) -> None:
        # metric lives in the spec (it must travel with the static search
        # config through jit/shard_map) but the index was built for ONE
        # metric — a mismatch would silently search with wrong distances.
        if spec.metric != self.metric:
            raise ValueError(
                f"spec.metric={spec.metric!r} but this Searcher was built "
                f"for {self.metric!r}; use searcher.spec(...) or pass "
                f"metric= explicitly"
            )

    def prepare(self, spec: SearchSpec):
        """Build (or fetch) the entry strategy's per-index state. Strategies
        exposing ``prepare_ctx`` get the whole searcher (attached hub lists,
        build provenance); the plain ``prepare`` protocol stays the
        extension point for external seeders."""
        strat = get_entry_strategy(spec.entry)
        cache_key = (spec.entry, spec.proj_dim, spec.hub_count)
        if cache_key not in self._aux:
            kp = jax.random.fold_in(
                self.key, zlib.crc32(spec.entry.encode()) & 0x7FFFFFFF
            )
            if hasattr(strat, "prepare_ctx"):
                self._aux[cache_key] = strat.prepare_ctx(self, spec, kp)
            else:
                self._aux[cache_key] = strat.prepare(
                    self.base, self.neighbors, self.hierarchy, spec, kp
                )
        return self._aux[cache_key]

    def seed(self, queries, spec: SearchSpec, key: jax.Array | None = None):
        """(Q, E) entry ids + (Q,) seed-phase comparisons."""
        self._check_metric(spec)
        strat = get_entry_strategy(spec.entry)
        aux = self.prepare(spec)
        if key is None:
            key = self.key
        return strat.seed(aux, queries, self.base, spec, key)

    def restart_keys(self, n_rows: int, spec: SearchSpec,
                     key: jax.Array | None = None) -> jax.Array | None:
        """Per-row restart keys for ``spec.restarts > 0`` (None otherwise):
        row i gets ``fold_in(key, i)`` — a function of the row INDEX, not the
        batch shape, so a request padded into a serving bucket draws the
        exact same restart seeds its rows would draw in a direct search."""
        if spec.restarts <= 0:
            return None
        if key is None:
            key = self.key
        return jax.vmap(lambda i: jax.random.fold_in(key, i))(
            jnp.arange(n_rows)
        )

    # -- scorers --------------------------------------------------------------

    @property
    def pq(self):
        """The PQ table this engine would serve WITHOUT training: the
        attached build-time table, else the single lazily trained cache
        entry, else None (what ``io.IndexArtifact.from_searcher`` persists
        so a reloaded index never re-runs k-means)."""
        if self._pq_attached is not None:
            return self._pq_attached
        if len(self._pq) == 1:
            return next(iter(self._pq.values()))
        return None

    def pq_index(self, spec: SearchSpec):
        """The (spec.pq_m, spec.pq_k) PQ code table, trained on first use
        from a key derived deterministically from the searcher's key (so a
        rebuilt engine reproduces the same codebooks bit-for-bit)."""
        from repro.baselines.pq import build_pq, derive_pq_key

        a = self._pq_attached
        if a is not None and (a.M, a.K) == (spec.pq_m, spec.pq_k):
            return a
        cache_key = (spec.pq_m, spec.pq_k, spec.pq_iters)
        if cache_key not in self._pq:
            self._pq[cache_key] = build_pq(
                self.base, M=spec.pq_m, K=spec.pq_k, iters=spec.pq_iters,
                key=derive_pq_key(self.key),
            )
        return self._pq[cache_key]

    def sq8_index(self):
        """The (codes, scale, mn) scalar-quantized base backing the ``sq8``
        scorer, quantized once per index (deterministic — a rebuilt or
        reloaded engine reproduces the identical table)."""
        if self._sq8 is None:
            from .scorers import build_sq8

            self._sq8 = build_sq8(self.base)
        return self._sq8

    def scorer_state(self, queries, spec: SearchSpec):
        """Per-batch operand pytree for ``spec.scorer`` (None for exact):
        the pq scorer pairs the code table with per-query ADC LUTs (queries
        rotated first when the table is OPQ-trained — the rotation is
        orthogonal, so rotated-space ADC ranks exactly like the unrotated
        metric); sq8 ships its quantized table + dequant params."""
        get_scorer(spec.scorer)  # unknown names fail loudly, pre-trace
        if spec.scorer == "sq8":
            idx = self.sq8_index()
            return (idx.codes, idx.scale, idx.mn)
        if spec.scorer != "pq":
            return None
        from repro.baselines.pq import build_adc_luts

        idx = self.pq_index(spec)
        q = queries if idx.rotation is None else queries @ idx.rotation
        luts = build_adc_luts(q, idx.codebooks, spec.metric)
        return (idx.codes, luts)

    # -- filtering & namespaces (DESIGN.md §14) -------------------------------

    def compiled_filter(self, fspec: FilterSpec) -> CompiledFilter:
        """``fspec`` evaluated against this index's metadata, cached per
        filter value in a ``filter_cache_size``-bounded LRU (default 64 —
        eviction costs a recompile on return, never correctness). Tombstoned
        rows are ANDed out of the allowed set at compile time, so the
        seed-redraw map and the exact-scan fallback never name a dead id
        (the deny bitmap still ORs with tombstones at ``_init_state`` —
        idempotent). MutableIndex rebuilds its Searcher on every mutation,
        so cached filters never go stale."""
        cached = self._filters.get(fspec)
        if cached is not None:
            self._filters.move_to_end(fspec)  # LRU: recent stays resident
            return cached
        cf = compile_filter(
            fspec, self.metadata, self.neighbors.shape[0],
            dead=self.tombstones,
        )
        self.filter_compiles += 1
        self._filters[fspec] = cf
        while len(self._filters) > self.filter_cache_size:
            self._filters.popitem(last=False)
        return cf

    def _filtered_brute(self, queries, cf: CompiledFilter, spec: SearchSpec,
                        *, q_valid: jax.Array | None = None) -> SearchResult:
        """Exact scan over the allowed set — the fallback for filters too
        selective to traverse (§14): the allowed subgraph of a very
        selective filter is near-edgeless, so instead of starving the beam
        we pay ``n_allowed`` exact comparisons, which at this selectivity is
        CHEAPER than a graph walk. Scores the float base directly whatever
        ``spec.scorer``/``spec.base_placement`` say (the allowed set is tiny
        by construction; recall is 1.0 by construction). ``allowed_ids`` is
        INVALID-padded to a power of two, so scan shapes — and compiled
        executables — are shared across filters of similar selectivity."""
        from repro.kernels import ops

        Q = queries.shape[0]
        allowed = cf.allowed_ids
        if spec.k > allowed.shape[0]:  # k answers need a >= k-wide scan
            allowed = jnp.concatenate([
                allowed,
                jnp.full((spec.k - allowed.shape[0],), INVALID, jnp.int32),
            ])
        ids = jnp.broadcast_to(allowed[None, :], (Q, allowed.shape[0]))
        d = ops.gather_distance(queries, ids, self.base, metric=spec.metric,
                                r_tile=spec.r_tile)  # INVALID -> +inf
        dd, sel = topk_smallest(d, spec.k)
        out = jnp.take_along_axis(ids, sel, axis=1)
        out = jnp.where(jnp.isfinite(dd), out, INVALID)
        comps = jnp.full((Q,), cf.n_allowed, jnp.int32)
        if q_valid is not None:  # §11 pad rows answer (INVALID, +inf, 0)
            out = jnp.where(q_valid[:, None], out, INVALID)
            dd = jnp.where(q_valid[:, None], dd, jnp.inf)
            comps = jnp.where(q_valid, comps, 0)
        return SearchResult(ids=out, dists=dd, n_comps=comps,
                            n_steps=jnp.int32(0),
                            # exact scan of the device float base: 4d bytes
                            # per comparison, same currency as _finalize
                            bytes_touched=comps * (4 * queries.shape[1]))

    def _filter_plan(self, spec: SearchSpec):
        """(CompiledFilter | None, route-to-brute bool) for ``spec``."""
        if spec.filter is None:
            return None, False
        cf = self.compiled_filter(spec.filter)
        return cf, cf.n_allowed <= filtered_brute_cutoff(spec)

    def _remap_entries(self, entries, cf: CompiledFilter | None,
                       key: jax.Array | None):
        """Seed redraw: seeds a filter denies, or that the tombstones mark
        dead, become uniform draws from the allowed (live) set — row-index-
        keyed, so served bucket-padded rows redraw bit-identically to direct
        search. Without it a row whose every seed is dead (likely when most
        of a mutable index's capacity is unallocated) answers nothing."""
        if cf is None and self.tombstones is not None:
            live = self.compiled_filter(FilterSpec())  # allowed = not dead
            if live.n_allowed < self.neighbors.shape[0]:
                cf = live
        if cf is None:
            return entries
        return remap_denied_seeds(
            entries, cf, self.key if key is None else key
        )

    # -- tiered base (DESIGN.md §9) -------------------------------------------

    def base_store(self, placement: str = "device",
                   dtype: str = "f32") -> BaseStore:
        """The base behind (``placement``, ``dtype``), built once and cached
        (a disk store spills the base to mmap'd temp shards on first use;
        under a true n >> RAM deployment construct the store from an
        artifact's shards via ``BaseStore.from_shards`` instead)."""
        check_placement(placement)
        ck = (placement, dtype)
        if ck not in self._stores:
            self._stores[ck] = BaseStore(self.base, placement, dtype=dtype)
        return self._stores[ck]

    def attach_store(self, store: BaseStore) -> BaseStore:
        """Adopt a pre-built tier store as this searcher's
        (placement, dtype) tier — the zero-copy path from a sharded
        artifact: ``attach_store(BaseStore.from_shards(*open_base_shards(
        path)))`` reranks straight off the mmap'd shard files instead of
        spilling the in-memory base (DESIGN.md §15)."""
        self._stores[(store.placement, store.dtype)] = store
        return store

    def _check_tier(self, spec: SearchSpec) -> None:
        check_placement(spec.base_placement)
        if spec.base_placement == "device":
            return
        sc = get_scorer(spec.scorer)
        if getattr(sc, "needs_base", True) or not sc.needs_rerank:
            raise ValueError(
                f"base_placement={spec.base_placement!r} traverses "
                "device-resident compressed state and reranks from the "
                f"backing tier; scorer={spec.scorer!r} reads the float base "
                "per hop — use a base-free scorer ('pq', 'sq8')"
            )

    def _host_start(self, queries, spec: SearchSpec,
                    key: jax.Array | None = None, *,
                    entries: jax.Array | None = None,
                    entry_comps: jax.Array | None = None,
                    q_valid: jax.Array | None = None,
                    cf: CompiledFilter | None = None) -> "_HostPending":
        """Device half of a host-tier search: seed, traverse on the code
        table, and ISSUE the async host->device gather of the top-``rerank``
        survivor rows. Returns a pending handle whose copy is in flight —
        finishing it later (``_host_finish``) lets the next tile's LUT build
        and traversal overlap the transfer (``search_stream``)."""
        self._check_metric(spec)
        self._check_tier(spec)
        store = self.base_store(spec.base_placement, spec.store_dtype)
        if entries is None:
            entries, entry_comps = self.seed(queries, spec, key)
        entries = self._remap_entries(entries, cf, key)
        if q_valid is not None and entry_comps is not None:
            entry_comps = jnp.where(q_valid, entry_comps, 0)
        state = self.scorer_state(queries, spec)
        trav = beam_traverse(
            queries, self.neighbors, entries,
            ef=spec.ef, metric=spec.metric, max_steps=spec.max_steps,
            expand_width=spec.expand_width, r_tile=spec.r_tile,
            scorer=spec.scorer, scorer_state=state, q_valid=q_valid,
            k=spec.k, term=spec.term, stable_steps=spec.stable_steps,
            restarts=spec.restarts, restart_gate=spec.restart_gate,
            restart_keys=self.restart_keys(queries.shape[0], spec, key),
            tombstones=self.tombstones,
            deny=None if cf is None else cf.deny,
        )
        cand = trav.cand_ids[:, :rerank_slice(spec.ef, spec.k, spec.rerank)]
        rows, tier_bytes = store.gather(cand)
        return _HostPending(spec=spec, queries=queries, trav=trav, cand=cand,
                            rows=rows, tier_bytes=tier_bytes,
                            scorer_state=state, entry_comps=entry_comps,
                            d=store.d)

    def _host_finish(self, p: "_HostPending") -> SearchResult:
        """Exact rerank over the gathered tier rows — same survivor slice,
        same distance formula, same comps bill as the device ``_finalize``,
        so every placement returns identical answers (f32 stores; bf16
        residuals trade the bit-parity for half the tier traffic).
        ``bytes_touched`` = the scorer's scored bytes (same as device) plus
        the tier's own billing for the rerank rows (row_bytes each on host,
        deduplicated 4 KiB pages on disk)."""
        dd, ids = rerank_gathered(p.queries, p.cand, p.rows, k=p.spec.k,
                                  metric=p.spec.metric)
        sc = get_scorer(p.spec.scorer)
        n_comps = sc.scale_comps(p.scorer_state, p.trav.n_comps, p.d)
        n_comps = n_comps + (p.cand >= 0).sum(axis=1, dtype=jnp.int32)
        if p.entry_comps is not None:
            n_comps = n_comps + p.entry_comps
        bytes_touched = (
            sc.scored_bytes(p.scorer_state, p.trav.n_comps, p.d)
            + p.tier_bytes
        )
        return SearchResult(ids=ids, dists=dd, n_comps=n_comps,
                            n_steps=p.trav.n_steps,
                            bytes_touched=bytes_touched)

    # -- search ---------------------------------------------------------------

    def search(self, queries, spec: SearchSpec, key: jax.Array | None = None,
               *, entries: jax.Array | None = None,
               entry_comps: jax.Array | None = None,
               q_valid: jax.Array | None = None) -> SearchResult:
        """Seed (unless ``entries`` pre-computed via :meth:`seed`) + beam.

        Passing ``entries``/``entry_comps`` lets benchmarks time the beam
        core separately from seed generation. ``q_valid`` (Q,) bool marks
        real rows of a bucket-padded batch (DESIGN.md §11): padding rows
        (False) seed all-INVALID, cost zero comparisons, and return
        (INVALID, +inf, 0) without perturbing real rows — the serving layer
        seeds each request on its real rows first (strategy parity), then
        pads queries/entries up to the bucket and masks here.

        ``spec.filter`` (DESIGN.md §14) restricts answers to a metadata
        predicate: its compiled deny bitmap ORs into the visited seeding (an
        operand — new filter values never recompile), denied seeds are
        redrawn from the allowed set, and filters selective past
        :func:`filtered_brute_cutoff` route to an exact scan of the allowed
        ids instead (``entries``/``scorer``/``base_placement`` are ignored
        on that fallback)."""
        self._check_metric(spec)
        cf, brute = self._filter_plan(spec)
        if brute:
            return self._filtered_brute(queries, cf, spec, q_valid=q_valid)
        if spec.base_placement != "device":
            return self._host_finish(self._host_start(
                queries, spec, key, entries=entries, entry_comps=entry_comps,
                q_valid=q_valid, cf=cf,
            ))
        if entries is None:
            entries, entry_comps = self.seed(queries, spec, key)
        entries = self._remap_entries(entries, cf, key)
        if q_valid is not None and entry_comps is not None:
            entry_comps = jnp.where(q_valid, entry_comps, 0)
        res = beam_search(
            queries, self.base, self.neighbors, entries,
            ef=spec.ef, k=spec.k, metric=spec.metric,
            max_steps=spec.max_steps, expand_width=spec.expand_width,
            r_tile=spec.r_tile, scorer=spec.scorer,
            scorer_state=self.scorer_state(queries, spec),
            rerank=spec.rerank, q_valid=q_valid,
            term=spec.term, stable_steps=spec.stable_steps,
            restarts=spec.restarts, restart_gate=spec.restart_gate,
            restart_keys=self.restart_keys(queries.shape[0], spec, key),
            tombstones=self.tombstones,
            deny=None if cf is None else cf.deny,
        )
        if entry_comps is not None:
            res = res._replace(n_comps=res.n_comps + entry_comps)
        return res

    def search_stream(self, queries, spec: SearchSpec,
                      key: jax.Array | None = None, *,
                      tile_q: int = 256) -> SearchResult:
        """Streaming query batching (DESIGN.md §7): a large Q is split into
        fixed ``tile_q``-row tiles that pipeline through the jitted beam core
        — one compile (the tile shape never changes; the last tile is padded),
        device-sized working sets, steady-state occupancy.

        Per-tile seeding keys are folded from ``key``, so key-deterministic
        strategies (projection / hierarchy / lsh) return exactly what
        :meth:`search` would; ``random`` draws per-tile seeds.
        ``n_steps`` sums the tiles' sequential loop iterations.

        Under ``base_placement='host'`` the tiles pipeline against the
        host->device rerank traffic: tile i's survivor-row copy is issued
        asynchronously, tile i+1 seeds / builds its LUTs / traverses while
        that copy is in flight, and only then is tile i's rerank finished —
        the §9 prefetch overlap."""
        self._check_metric(spec)
        Q = queries.shape[0]
        if Q <= tile_q:
            return self.search(queries, spec, key)
        if key is None:
            key = self.key
        self.prepare(spec)  # strategy state built once, outside the loop
        if spec.scorer == "pq":
            self.pq_index(spec)  # code table trained once, outside the loop
        elif spec.scorer == "sq8":
            self.sq8_index()     # table quantized once, outside the loop
        cf, brute = self._filter_plan(spec)  # compiled once, every tile
        # a brute-routed filter ignores placement — tiles go through
        # self.search's fallback, not the host pipeline
        tiered = spec.base_placement != "device" and not brute
        ids, dists, comps, tbytes = [], [], [], []
        n_steps = jnp.int32(0)
        pending: tuple[_HostPending, int] | None = None

        def finish(p: _HostPending, take: int):
            nonlocal n_steps
            res = self._host_finish(p)
            ids.append(res.ids[:take])
            dists.append(res.dists[:take])
            comps.append(res.n_comps[:take])
            tbytes.append(res.bytes_touched[:take])
            n_steps = n_steps + res.n_steps

        for i, lo in enumerate(range(0, Q, tile_q)):
            tile = queries[lo:lo + tile_q]
            pad = tile_q - tile.shape[0]
            if pad:  # keep the compiled shape fixed; padding rows are masked
                # out via q_valid (§11) so they cost zero comparisons instead
                # of redundantly re-searching the last real row
                tile = jnp.concatenate(
                    [tile, jnp.zeros((pad, tile.shape[1]), tile.dtype)]
                )
            take = tile_q - pad
            valid = jnp.arange(tile_q) < take
            kt = jax.random.fold_in(key, i)
            if tiered:
                p = self._host_start(tile, spec, kt, q_valid=valid,
                                     cf=cf)  # copy now in flight
                if pending is not None:
                    finish(*pending)  # previous tile, its copy long overlapped
                pending = (p, take)
                continue
            res = self.search(tile, spec, kt, q_valid=valid)
            ids.append(res.ids[:take])
            dists.append(res.dists[:take])
            comps.append(res.n_comps[:take])
            tbytes.append(res.bytes_touched[:take])
            n_steps = n_steps + res.n_steps
        if pending is not None:
            finish(*pending)
        return SearchResult(
            ids=jnp.concatenate(ids),
            dists=jnp.concatenate(dists),
            n_comps=jnp.concatenate(comps),
            n_steps=n_steps,
            bytes_touched=jnp.concatenate(tbytes),
        )

    def search_with_trace(self, queries, spec: SearchSpec,
                          key: jax.Array | None = None,
                          max_steps: int | None = None):
        """Fig. 6 instrumentation through the same seeding path.
        ``spec.max_steps`` (when set) overrides ``max_steps``; when both are
        unset the core's expand_width-aware default applies."""
        if spec.base_placement != "device":
            # the fixed-step scan reranks inside jit — instrumentation is a
            # device-resident tool; tiered runs trace with placement="device"
            raise ValueError(
                "search_with_trace requires base_placement='device'"
            )
        cf, brute = self._filter_plan(spec)
        if brute:
            raise ValueError(
                "search_with_trace traces the graph walk; this filter "
                "routes to the exact-scan fallback (n_allowed <= "
                f"{filtered_brute_cutoff(spec)}) — loosen the filter or "
                "trace unfiltered"
            )
        ent, extra = self.seed(queries, spec, key)
        ent = self._remap_entries(ent, cf, key)
        if spec.max_steps is not None:
            max_steps = spec.max_steps
        res, td, tc = search_with_trace(
            queries, self.base, self.neighbors, ent,
            ef=spec.ef, k=spec.k, metric=spec.metric, max_steps=max_steps,
            expand_width=spec.expand_width, r_tile=spec.r_tile,
            scorer=spec.scorer,
            scorer_state=self.scorer_state(queries, spec),
            rerank=spec.rerank,
            term=spec.term, stable_steps=spec.stable_steps,
            restarts=spec.restarts, restart_gate=spec.restart_gate,
            restart_keys=self.restart_keys(queries.shape[0], spec, key),
            tombstones=self.tombstones,
            deny=None if cf is None else cf.deny,
        )
        return res._replace(n_comps=res.n_comps + extra), td, tc + extra[None, :]


# -- shard-level plumbing (the distributed layer runs THIS engine per shard) --


def globalize_ids(ids: jax.Array, shard_id, per: int) -> jax.Array:
    """Local row ids -> global ids for contiguous shard ``shard_id``."""
    return jnp.where(ids >= 0, ids + shard_id * per, INVALID)


def merge_shard_results(dists: jax.Array, ids: jax.Array,
                        k: int) -> tuple[jax.Array, jax.Array]:
    """(Q, P*k) gathered per-shard answers -> the k global best, ascending."""
    md, sel = topk_smallest(dists, k)
    return md, jnp.take_along_axis(ids, sel, axis=1)


def shard_entries(key: jax.Array, n_shards: int, Q: int, per: int,
                  E: int) -> jax.Array:
    """Random per-shard seeds — the engine's ``random`` strategy drawn once
    per shard (each shard's graph is its own id space)."""
    return jax.random.randint(key, (n_shards, Q, E), 0, per, dtype=jnp.int32)


def shard_search(queries, base, neighbors, entries, live, *, spec: SearchSpec,
                 axis: str, per: int, scorer_state=None, restart_keys=None,
                 deny=None):
    """Per-shard body for ``shard_map``: the SAME beam core as single-host
    search, plus the all-gather merge. ``live`` False drops a failed or
    straggling shard's contribution (degrades recall, never the query).
    ``scorer_state`` is this shard's operand pytree for ``spec.scorer``
    (e.g. its local PQ codes + the batch LUTs); the rerank inside
    ``beam_search`` runs against the local base, so merged distances are
    exact regardless of scorer. ``spec.term``/``spec.restarts`` reach the
    shard's beam unchanged (``restart_keys`` (Q, 2) per-row keys required
    when restarts > 0 — replicate the same keys to every shard). ``deny``
    (optional) is THIS shard's packed filter bitmap over its local id space
    (§14): compile the filter against each shard's metadata slice; entries
    must already be filter-valid (remap per shard before calling)."""
    if spec.base_placement != "device":
        raise ValueError(
            "shard_search reranks in-shard against a device-resident base; "
            "for base_placement='host' use shard_traverse + the caller-side "
            "host rerank (distributed_search(base_placement='host'))"
        )
    res = beam_search(
        queries, base, neighbors, entries,
        ef=spec.ef, k=spec.k, metric=spec.metric,
        max_steps=spec.max_steps, expand_width=spec.expand_width,
        r_tile=spec.r_tile, scorer=spec.scorer, scorer_state=scorer_state,
        rerank=spec.rerank,
        term=spec.term, stable_steps=spec.stable_steps,
        restarts=spec.restarts, restart_gate=spec.restart_gate,
        restart_keys=restart_keys, deny=deny,
    )
    sid = jax.lax.axis_index(axis)
    gids = globalize_ids(res.ids, sid, per)
    d = jnp.where(live, res.dists, jnp.inf)
    gids = jnp.where(live, gids, INVALID)
    all_d = jax.lax.all_gather(d, axis)            # (P, Q, k) — tiny
    all_i = jax.lax.all_gather(gids, axis)
    Pn = all_d.shape[0]
    Q = queries.shape[0]
    flat_d = all_d.transpose(1, 0, 2).reshape(Q, Pn * spec.k)
    flat_i = all_i.transpose(1, 0, 2).reshape(Q, Pn * spec.k)
    md, mi = merge_shard_results(flat_d, flat_i, spec.k)
    comps = jax.lax.psum(jnp.where(live, res.n_comps, 0), axis)
    return md, mi, comps


def shard_traverse(queries, neighbors, entries, live, *, spec: SearchSpec,
                   axis: str, per: int, r: int, scorer_state,
                   restart_keys=None, deny=None):
    """Per-shard body for the HOST-TIER distributed path (DESIGN.md §9):
    traverse on the shard's device-resident code table only (no float base
    operand at all), globalize the top-``r`` ADC survivors, and all-gather
    them — the exact rerank runs OUTSIDE shard_map against the host
    :class:`~repro.core.base_store.BaseStore`, which holds the one global
    float base no shard could fit.

    Returns ((Q, P*r) replicated global survivor ids, (Q,) psum'd RAW
    scored-id counts — the caller scales them to the paper's currency once
    it knows the store's d)."""
    trav = beam_traverse(
        queries, neighbors, entries,
        ef=spec.ef, metric=spec.metric, max_steps=spec.max_steps,
        expand_width=spec.expand_width, r_tile=spec.r_tile,
        scorer=spec.scorer, scorer_state=scorer_state,
        k=spec.k, term=spec.term, stable_steps=spec.stable_steps,
        restarts=spec.restarts, restart_gate=spec.restart_gate,
        restart_keys=restart_keys, deny=deny,
    )
    sid = jax.lax.axis_index(axis)
    gids = globalize_ids(trav.cand_ids[:, :r], sid, per)
    gids = jnp.where(live, gids, INVALID)  # dead shard -> no survivors
    all_i = jax.lax.all_gather(gids, axis)               # (P, Q, r) — tiny
    Pn = all_i.shape[0]
    Q = queries.shape[0]
    flat_i = all_i.transpose(1, 0, 2).reshape(Q, Pn * r)
    comps = jax.lax.psum(jnp.where(live, trav.n_comps, 0), axis)
    return flat_i, comps


def emulated_shard_search(queries, base_shards, nbr_shards, entries, live,
                          spec: SearchSpec, scorer_states=None,
                          restart_keys=None, denies=None):
    """Host-side loop with identical semantics to ``shard_search`` for runs
    where logical shards exceed physical devices (CI, laptops).
    ``scorer_states`` (optional) is a per-shard list of scorer operands;
    ``denies`` (optional) a per-shard list of packed filter bitmaps (§14).

    Returns (dists (Q, k), global ids (Q, k))."""
    if spec.base_placement != "device":
        raise ValueError(
            "emulated_shard_search reranks in-shard against device-resident "
            "base shards; the host tier goes through "
            "distributed_search(base_placement='host')"
        )
    per = base_shards.shape[1]
    all_d, all_i = [], []
    for s in range(base_shards.shape[0]):
        res = beam_search(
            queries, base_shards[s], nbr_shards[s], entries[s],
            ef=spec.ef, k=spec.k, metric=spec.metric,
            max_steps=spec.max_steps, expand_width=spec.expand_width,
            r_tile=spec.r_tile, scorer=spec.scorer,
            scorer_state=None if scorer_states is None else scorer_states[s],
            rerank=spec.rerank,
            term=spec.term, stable_steps=spec.stable_steps,
            restarts=spec.restarts, restart_gate=spec.restart_gate,
            restart_keys=restart_keys,
            deny=None if denies is None else denies[s],
        )
        all_d.append(jnp.where(live[s], res.dists, jnp.inf))
        all_i.append(jnp.where(live[s], globalize_ids(res.ids, s, per), INVALID))
    return merge_shard_results(
        jnp.concatenate(all_d, 1), jnp.concatenate(all_i, 1), spec.k
    )
