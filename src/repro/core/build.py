"""Unified build pipeline — BuildSpec × (construct · diversify · compress).

The paper's central claim is about *build-time* choices: a flat k-NN graph
plus diversification matches the hierarchy's search speed (Sec. IV). This
module makes construction a first-class composable axis, mirroring the
search side's entry-strategy/scorer registries (DESIGN.md §3, §8):

* **construct** — how the raw neighborhood graph is obtained:
  ``nndescent`` (KGraph's NN-Descent), ``exact`` (brute-force k-NN — the
  oracle for small worlds), ``hnsw`` (the layered index; its bottom layer is
  the flat graph and its upper layers feed the ``hierarchy`` seeder).
* **diversify** — the paper's edge-selection schemes over that graph:
  ``none``, ``gd`` (occlusion pruning, Fig. 2), ``dpg`` (angular max-min),
  each with the reverse-edge policy (``union`` | ``none``) as a knob.
* **compress** — build-time vector compression backing the ``pq`` scorer:
  ``none`` | ``pq`` (codebooks trained and codes encoded AT BUILD TIME with
  the same key derivation the engine's lazy path uses, so an attached table
  is bit-identical to a lazily trained one) | ``opq`` (PQ behind a learned
  orthogonal rotation [Ge CVPR'13] — same artifact slot, closes the d>=64
  recall gap plain PQ shows; DESIGN.md §15).

``GraphBuilder(spec).build(base, key)`` composes the three stages and emits a
:class:`BuildReport` (rounds, update curve, realized degree distribution,
dropped reverse edges, graph-recall proxy, walls, memory) — the provenance
that rides the on-disk :class:`~repro.core.io.IndexArtifact` and the
``build_sweep`` benchmark rows. New stages plug in via the ``register_*``
functions and never touch the engine or its callers (DESIGN.md §10).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from .graph_index import (
    DEFAULT_N_HUBS,
    HnswIndex,
    KnnGraph,
    degree_distribution,
    hub_vertices,
    in_degree_distribution,
    memory_bytes,
    pad_neighbors,
)
from .topk import INVALID

REVERSE_POLICIES = ("union", "none")


class BuildSpec(NamedTuple):
    """Static build configuration (hashable leaves, JSON-able via _asdict).

    One spec drives every build surface: ``GraphBuilder``/``Searcher.build``,
    the per-shard bodies of ``distributed.shard_build``, the serving
    launcher's ``--build-*`` flags, and the ``build_sweep`` benchmark. Zero
    values of ``hnsw_m`` / ``max_keep`` / ``max_degree`` mean "stage
    default" (they must stay int-typed for hashability)."""

    construct: str = "nndescent"   # key into CONSTRUCTORS
    diversify: str = "gd"          # key into DIVERSIFIERS
    compress: str = "none"         # key into COMPRESSORS
    metric: str = "l2"
    graph_k: int = 20              # raw k-NN degree out of the construct stage
    # construct knobs
    nd_rounds: int = 15            # NN-Descent round budget
    nd_delta: float = 0.002        # early-termination update-rate threshold
    hnsw_m: int = 0                # upper-layer degree (0 = max(8, graph_k/2))
    # diversify knobs
    max_keep: int = 0              # survivors per vertex (0 = L/2, the paper)
    max_degree: int = 0            # post-union degree cap (0 = stage default)
    reverse: str = "union"         # reverse-edge policy: union | none
    # compress knobs (match SearchSpec's pq_* so specs can be zipped)
    pq_m: int = 8                  # PQ sub-vectors (bytes/vector of the codes)
    pq_k: int = 256                # PQ codewords per sub-quantizer
    pq_iters: int = 15             # k-means iterations at PQ train time
    opq_iters: int = 6             # rotation/codebook alternations (opq only)
    # report knobs
    proxy_sample: int = 256        # vertices sampled for the graph-recall
                                   # proxy (0 disables the check)
    n_hubs: int = DEFAULT_N_HUBS   # top in-degree vertices derived for the
                                   # hubs seeder (persisted in the artifact)
    lid_sample: int = 256          # points sampled for the Levina–Bickel
                                   # LID estimate (0 disables; paper Tab. I)
    insert_ef: int = 64            # construct='incremental' beam width per
                                   # insert (0 = exact-scan maintenance: the
                                   # streaming build then bit-matches
                                   # construct='exact' — DESIGN.md §13)


class ConstructResult(NamedTuple):
    """Output of one construct stage: the flat graph the beam walks, the
    optional hierarchy behind the ``hierarchy`` seeder, and JSON-able
    provenance (rounds, update curve, layer sizes, ...).

    ``proxy_graph`` (optional) is the graph the recall proxy should score
    when it differs from ``graph``: the hnsw constructor's bottom layer is
    already occlusion-pruned, so the proxy measures its RAW NN-Descent
    graph instead — keeping the ``build_sweep`` proxy column comparable
    across constructs (same quantity: raw construction quality, never the
    diversifier's edge selection)."""

    graph: KnnGraph
    hierarchy: HnswIndex | None
    stats: dict
    proxy_graph: KnnGraph | None = None


CONSTRUCTORS: dict[str, Callable] = {}
DIVERSIFIERS: dict[str, Callable] = {}
COMPRESSORS: dict[str, Callable] = {}


def _get(registry: dict, kind: str, name: str):
    if name not in registry:
        raise ValueError(
            f"unknown {kind} stage {name!r}; registered: {sorted(registry)}"
        )
    return registry[name]


def register_constructor(name: str):
    """Register ``fn(base, spec, key, verbose) -> ConstructResult``."""
    def deco(fn):
        CONSTRUCTORS[name] = fn
        return fn
    return deco


def register_diversifier(name: str):
    """Register ``fn(base, graph, spec) -> (KnnGraph, stats dict)``; stats
    must carry ``dropped_reverse_edges`` (0 when the stage drops nothing)."""
    def deco(fn):
        DIVERSIFIERS[name] = fn
        return fn
    return deco


def register_compressor(name: str):
    """Register ``fn(base, spec, key) -> PQIndex | None``."""
    def deco(fn):
        COMPRESSORS[name] = fn
        return fn
    return deco


# -- construct stages ---------------------------------------------------------


def _nd_config(spec: BuildSpec):
    from .nndescent import NNDescentConfig

    cfg = NNDescentConfig(k=spec.graph_k, rounds=spec.nd_rounds,
                          delta=spec.nd_delta)
    # the local join samples at most k neighbors per list — clamp the default
    # sample widths for small-degree builds (no-op at the k >= 12 defaults)
    return cfg._replace(sample=min(cfg.sample, spec.graph_k),
                        sample_nn=min(cfg.sample_nn, spec.graph_k))


@register_constructor("nndescent")
def _construct_nndescent(base, spec: BuildSpec, key, verbose) -> ConstructResult:
    from .nndescent import build_knn_graph_with_stats

    graph, st = build_knn_graph_with_stats(base, _nd_config(spec),
                                           metric=spec.metric,
                                           key=key, verbose=verbose)
    return ConstructResult(graph, None, {
        "rounds": st.rounds, "update_curve": list(st.update_curve),
        "converged": st.converged,
    })


@register_constructor("exact")
def _construct_exact(base, spec: BuildSpec, key, verbose) -> ConstructResult:
    from .bruteforce import exact_knn_graph

    k = min(spec.graph_k, base.shape[0] - 1)
    graph = exact_knn_graph(base, k, metric=spec.metric)
    return ConstructResult(graph, None,
                           {"rounds": 0, "update_curve": [], "converged": True})


@register_constructor("incremental")
def _construct_incremental(base, spec: BuildSpec, key, verbose
                           ) -> ConstructResult:
    """Streaming construction (DESIGN.md §13): every point arrives through
    ``MutableIndex.insert`` — beam-search-then-link (``spec.insert_ef > 0``)
    with the ``spec.diversify`` stage applied INLINE per insert, or exact-
    scan maintenance (``insert_ef = 0``), which makes N inserts bit-identical
    to ``construct='exact'`` at matched capacity (the golden equivalence in
    tests/test_mutable.py). Diversification being inline, ``GraphBuilder``
    skips the global diversify stage (``stats['inline_diversify']``)."""
    import numpy as np

    from .mutable import MutableIndex

    n, d = base.shape
    idx = MutableIndex.empty(
        d, min(spec.graph_k, max(n - 1, 1)), capacity=n, metric=spec.metric,
        key=key, insert_ef=spec.insert_ef, diversify=spec.diversify,
        max_keep=spec.max_keep,
    )
    t0 = time.perf_counter()
    idx.insert_batch(np.asarray(base, np.float32))
    wall = time.perf_counter() - t0
    return ConstructResult(idx.live_graph(), None, {
        "rounds": 0, "update_curve": [], "converged": True,
        "inline_diversify": spec.diversify, "inserts": n,
        "insert_rate": round(n / max(wall, 1e-9), 1),
    })


@register_constructor("hnsw")
def _construct_hnsw(base, spec: BuildSpec, key, verbose) -> ConstructResult:
    """Layered construction: NN-Descent bottom graph shared into
    ``build_hnsw`` (the pre-refactor ``Searcher.build(with_hierarchy=True)``
    flow, bit-identical for equal keys). The bottom layer IS the flat graph
    — HNSW occlusion-prunes every layer itself, so this constructor pairs
    with ``diversify='none'`` (enforced by :class:`GraphBuilder`)."""
    from .hnsw import HnswConfig, build_hnsw_with_stats
    from .nndescent import build_knn_graph_with_stats

    g, st = build_knn_graph_with_stats(base, _nd_config(spec),
                                       metric=spec.metric, key=key,
                                       verbose=verbose)
    m = spec.hnsw_m or max(8, spec.graph_k // 2)
    idx, layers = build_hnsw_with_stats(
        base, HnswConfig(M=m, knn_k=spec.graph_k), metric=spec.metric,
        key=key, bottom_graph=g, verbose=verbose,
    )
    dropped = sum(l["dropped_reverse_edges"] for l in layers)
    return ConstructResult(idx.bottom_graph(), idx, {
        "rounds": st.rounds, "update_curve": list(st.update_curve),
        "converged": st.converged, "layers": layers,
        "dropped_reverse_edges": dropped,
    }, proxy_graph=g)


# -- diversify stages ---------------------------------------------------------


def _check_reverse(spec: BuildSpec) -> None:
    if spec.reverse not in REVERSE_POLICIES:
        raise ValueError(
            f"unknown reverse-edge policy {spec.reverse!r}; one of "
            f"{REVERSE_POLICIES}"
        )


def _truncation_drops(neighbors, max_degree: int) -> int:
    """Valid edges a ``pad_neighbors`` cap would evict (rows are compacted
    by the prunes, so the overflow is exactly the tail past the cap)."""
    if max_degree >= neighbors.shape[1]:
        return 0
    return int((neighbors[:, max_degree:] != INVALID).sum())


def _finish_prune(kept, spec: BuildSpec, default_degree: int):
    """Shared tail of gd/dpg: reverse-edge policy + cap + accounting. Both
    policies count cap evictions — edges the unbounded paper scheme would
    have kept are never dropped silently."""
    from .diversify import ReverseUnionStats, add_reverse_edges_with_stats

    max_degree = spec.max_degree or default_degree
    if spec.reverse == "union":
        merged, rstats = add_reverse_edges_with_stats(kept, max_degree)
    else:
        rstats = ReverseUnionStats(
            candidates=0, dropped_slot=0,
            dropped_cap=_truncation_drops(kept, max_degree),
        )
        merged = pad_neighbors(kept, max_degree)
    graph = KnnGraph(neighbors=merged, dists=jnp.full(merged.shape, jnp.nan))
    return graph, {
        "dropped_reverse_edges": rstats.dropped,
        "reverse_candidates": rstats.candidates,
    }


@register_diversifier("none")
def _diversify_none(base, graph: KnnGraph, spec: BuildSpec):
    dropped = 0
    if spec.max_degree and spec.max_degree != graph.degree:
        dropped = _truncation_drops(graph.neighbors, spec.max_degree)
        nbrs = pad_neighbors(graph.neighbors, spec.max_degree)
        graph = KnnGraph(neighbors=nbrs,
                         dists=jnp.full(nbrs.shape, jnp.nan))
    return graph, {"dropped_reverse_edges": dropped, "reverse_candidates": 0}


@register_diversifier("gd")
def _diversify_gd(base, graph: KnnGraph, spec: BuildSpec):
    """The paper's hybrid scheme (KGraph+GD): occlusion prune + reverse
    union, default cap L (``build_gd_graph`` parity)."""
    from .diversify import gd_prune

    kept = gd_prune(base, graph, max_keep=spec.max_keep or None,
                    metric=spec.metric)
    return _finish_prune(kept, spec, default_degree=graph.degree)


@register_diversifier("dpg")
def _diversify_dpg(base, graph: KnnGraph, spec: BuildSpec):
    """DPG [Li TKDE'19]: angular max-min + reverse union, default cap
    2 * keeps — DPG keeps the full union, ~2x GD's index size
    (``build_dpg_graph`` parity)."""
    from .diversify import dpg_prune

    kept = dpg_prune(base, graph, max_keep=spec.max_keep or None)
    default_degree = 2 * (spec.max_keep or graph.degree // 2)
    return _finish_prune(kept, spec, default_degree=default_degree)


# -- compress stages ----------------------------------------------------------


@register_compressor("none")
def _compress_none(base, spec: BuildSpec, key):
    return None


@register_compressor("pq")
def _compress_pq(base, spec: BuildSpec, key):
    """Train codebooks / encode codes at build time. ``derive_pq_key`` is
    the engine's lazy-path derivation (``Searcher.pq_index``), so the
    attached table a build ships is bit-identical to what a fresh engine
    with the same key would train on first use — round-tripping an artifact
    can therefore never flip a search result."""
    from repro.baselines.pq import build_pq, derive_pq_key

    return build_pq(base, M=spec.pq_m, K=spec.pq_k, iters=spec.pq_iters,
                    key=derive_pq_key(key))


@register_compressor("opq")
def _compress_opq(base, spec: BuildSpec, key):
    """OPQ: alternate codebook training with a closed-form orthogonal
    Procrustes rotation (DESIGN.md §15). The rotation rides the artifact
    (``pq_rotation``) and the engine rotates queries in ``scorer_state``;
    ``derive_opq_key`` keeps the trajectory deterministic and distinct from
    the plain-pq derivation."""
    from repro.baselines.pq import build_opq, derive_opq_key

    return build_opq(base, M=spec.pq_m, K=spec.pq_k, iters=spec.pq_iters,
                     key=derive_opq_key(key), opq_iters=spec.opq_iters)


# -- report -------------------------------------------------------------------


@dataclasses.dataclass
class BuildReport:
    """Provenance + quality accounting of one build (JSON-able via
    :meth:`summary`; persisted inside the artifact manifest and emitted as
    ``build_sweep`` rows)."""

    spec: BuildSpec
    n: int
    d: int
    rounds: int                       # NN-Descent rounds executed (0 = exact)
    update_curve: tuple[int, ...]     # per-round new-entry counts
    converged: bool                   # early-termination fired
    graph_recall_proxy: float         # sampled fraction of true k-NN edges
                                      # present in the CONSTRUCTED graph
                                      # (-1.0 when proxy_sample=0)
    degree: dict                      # realized degree distribution (final)
    dropped_reverse_edges: int        # slot overflow + cap evictions
    wall_construct_s: float
    wall_diversify_s: float
    wall_compress_s: float
    wall_total_s: float
    memory_bytes: int                 # graph/hierarchy + PQ tables
    layers: list = dataclasses.field(default_factory=list)  # hnsw per-layer
    # realized in-degree distribution of the final graph — out-degree is
    # capped by construction, in-degree is where the hub mass shows
    in_degree: dict = dataclasses.field(default_factory=dict)
    # top-n_hubs vertices by in-degree (descending), backing the "hubs"
    # entry strategy; JSON-able so the artifact manifest carries provenance
    hub_ids: list = dataclasses.field(default_factory=list)
    # Levina–Bickel MLE local intrinsic dimensionality of the base (paper
    # Tab. I's curse-of-dimensionality diagnostic; -1.0 when lid_sample=0)
    lid: float = -1.0
    # streaming-mutation metrics (DESIGN.md §13): points absorbed through
    # MutableIndex.insert (construct='incremental', or the mutation cycle a
    # compaction merged), their sustained rate, and the staleness fraction
    # the build/compaction cleared (0.0 for batch constructs)
    inserts: int = 0
    insert_rate: float = -1.0
    staleness: float = 0.0

    def summary(self) -> dict:
        d = dataclasses.asdict(self)
        d["spec"] = self.spec._asdict()
        d["update_curve"] = list(self.update_curve)
        return d


class BuildResult(NamedTuple):
    """What one ``GraphBuilder.build`` hands back: everything a
    ``Searcher`` (or an on-disk artifact) is made of."""

    graph: KnnGraph
    hierarchy: HnswIndex | None
    pq: object | None             # baselines.pq.PQIndex
    report: BuildReport
    hubs: jax.Array | None = None  # (n_hubs,) int32, in-degree descending

    @property
    def neighbors(self) -> jax.Array:
        return self.graph.neighbors


def graph_recall_proxy(base, graph: KnnGraph, metric: str = "l2",
                       k: int = 10, sample: int = 256) -> float:
    """Sampled graph quality: fraction of true k-NN edges present in the
    adjacency, measured on ``sample`` evenly spaced vertices (deterministic,
    no key). The KGraph quality metric without the O(n^2) exact graph —
    cheap enough to run on every build and gate in CI."""
    n = graph.n
    k = min(k, graph.degree, n - 1)
    s = min(sample, n)
    return float(_proxy_hits(base, graph.neighbors, metric, k, s))


@functools.partial(jax.jit, static_argnames=("metric", "k", "s"))
def _proxy_hits(base, neighbors, metric: str, k: int, s: int):
    from .bruteforce import exact_search

    n = neighbors.shape[0]
    rows = jnp.arange(s, dtype=jnp.int32) * (n // s)
    # k+1 then drop self by id (robust for non-l2 metrics)
    _, ids = exact_search(base[rows], base, k + 1, metric)
    notself = ids != rows[:, None]
    order = jnp.argsort(~notself, axis=1, stable=True)
    exact_ids = jnp.take_along_axis(ids, order, axis=1)[:, :k]
    nbrs = neighbors[rows]
    hit = (exact_ids[:, :, None] == nbrs[:, None, :]).any(-1)
    return hit.mean()


# -- the builder --------------------------------------------------------------


class GraphBuilder:
    """(construct · diversify · compress), validated up front.

    Stage names are resolved at construction time so a typo fails before any
    NN-Descent rounds burn; ``build`` runs the three stages, times each, and
    assembles the :class:`BuildReport`. The construct and diversify stages
    are ``ann.construct`` / ``ann.diversify`` spans in a profiler trace."""

    def __init__(self, spec: BuildSpec):
        self.spec = spec
        self._construct = _get(CONSTRUCTORS, "construct", spec.construct)
        self._diversify = _get(DIVERSIFIERS, "diversify", spec.diversify)
        self._compress = _get(COMPRESSORS, "compress", spec.compress)
        _check_reverse(spec)
        if spec.construct == "hnsw" and spec.diversify != "none":
            raise ValueError(
                "construct='hnsw' occlusion-prunes every layer at build "
                "time; composing a second diversify stage would desync the "
                "bottom layer from the hierarchy — use diversify='none' "
                "(sweep flat constructs against gd/dpg instead)"
            )

    def build(self, base, key: jax.Array | None = None,
              verbose: bool = False) -> BuildResult:
        spec = self.spec
        if key is None:
            key = jax.random.PRNGKey(0)
        if spec.compress in ("pq", "opq") and base.shape[1] % spec.pq_m:
            raise ValueError(
                f"compress={spec.compress!r} needs d % pq_m == 0 "
                f"(d={base.shape[1]}, pq_m={spec.pq_m})"
            )

        with jax.profiler.TraceAnnotation("ann.construct"):
            t0 = time.perf_counter()
            cres = self._construct(base, spec, key, verbose)
            jax.block_until_ready(cres.graph.neighbors)
            t1 = time.perf_counter()

        proxy = -1.0
        if spec.proxy_sample:
            proxy_graph = (cres.proxy_graph if cres.proxy_graph is not None
                           else cres.graph)
            proxy = graph_recall_proxy(base, proxy_graph, metric=spec.metric,
                                       sample=spec.proxy_sample)

        with jax.profiler.TraceAnnotation("ann.diversify"):
            t2 = time.perf_counter()
            if cres.stats.get("inline_diversify"):
                # the construct diversified per insert (incremental); a
                # second global pass would double-prune the same edges
                graph, dstats = cres.graph, {"dropped_reverse_edges": 0}
            else:
                graph, dstats = self._diversify(base, cres.graph, spec)
            jax.block_until_ready(graph.neighbors)
            t3 = time.perf_counter()

        pq = self._compress(base, spec, key)
        if pq is not None:
            jax.block_until_ready(pq.codes)
        t4 = time.perf_counter()

        dropped = (dstats["dropped_reverse_edges"]
                   + cres.stats.get("dropped_reverse_edges", 0))
        mem = memory_bytes(cres.hierarchy if cres.hierarchy is not None
                           else graph.neighbors)
        if pq is not None:
            mem += memory_bytes((pq.codebooks, pq.codes))

        # hub derivation off the FINAL adjacency (post-diversify): the walk
        # the hubs seeder feeds runs on this graph, so its in-degree heavy
        # tail is the one that matters
        hubs = hub_vertices(graph.neighbors, spec.n_hubs)

        lid = -1.0
        if spec.lid_sample:
            from .lid import lid_mle

            # always Euclidean: LID is a geometric property of the point
            # set (paper Tab. I), independent of the search metric
            lid = float(lid_mle(
                base, k=min(20, base.shape[0] - 2),
                sample=spec.lid_sample, metric="l2",
                key=jax.random.fold_in(key, 0x11D),
            ))

        report = BuildReport(
            spec=spec, n=base.shape[0], d=base.shape[1],
            rounds=cres.stats.get("rounds", 0),
            update_curve=tuple(cres.stats.get("update_curve", ())),
            converged=cres.stats.get("converged", True),
            graph_recall_proxy=round(proxy, 4),
            degree=degree_distribution(graph.neighbors),
            dropped_reverse_edges=int(dropped),
            wall_construct_s=round(t1 - t0, 4),
            wall_diversify_s=round(t3 - t2, 4),
            wall_compress_s=round(t4 - t3, 4),
            wall_total_s=round((t1 - t0) + (t3 - t2) + (t4 - t3), 4),
            memory_bytes=int(mem),
            layers=cres.stats.get("layers", []),
            in_degree=in_degree_distribution(graph.neighbors),
            hub_ids=[int(h) for h in hubs],
            lid=round(lid, 2),
            inserts=int(cres.stats.get("inserts", 0)),
            insert_rate=float(cres.stats.get("insert_rate", -1.0)),
        )
        return BuildResult(graph=graph, hierarchy=cres.hierarchy, pq=pq,
                           report=report, hubs=hubs)


def build_index(base, spec: BuildSpec = BuildSpec(),
                key: jax.Array | None = None,
                verbose: bool = False) -> BuildResult:
    """One-call convenience: ``GraphBuilder(spec).build(base, key)``."""
    return GraphBuilder(spec).build(base, key=key, verbose=verbose)
