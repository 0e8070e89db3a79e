"""Distributed graph-ANN serving: shard-and-merge (DESIGN.md §4).

The base matrix and its (flat, diversified) graph are sharded over every mesh
axis flattened into one logical 'shards' axis: device p owns rows
[p*n/P, (p+1)*n/P) and the graph rows restricted to *local* targets (the
builder relabels cross-shard edges to local approximations — standard for
shard-per-machine ANN deployments; recall cost is measured in tests).

Queries are replicated; each shard runs the batched beam search on its local
graph; the global answer is an all-gather of (k, dist) pairs + local merge
(k * P values — tiny). A lost/straggling shard degrades recall by ~n/P
candidates instead of failing the query: ``live_mask`` drops its
contribution (straggler mitigation by design).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from repro.core import engine
from repro.core.base_store import BaseStore, check_placement, rerank_gathered
from repro.core.beam_search import rerank_slice
from repro.core.engine import SearchSpec


class ShardBuildResult(NamedTuple):
    """Output of :func:`shard_build` — drop-in operands for the existing
    search paths: ``(base_shards, nbr_shards)`` feed ``distributed_search``
    / ``shard_search`` / ``emulated_shard_search`` unchanged, the PQ stacks
    (when ``spec.compress='pq'``) feed ``scorer='pq'`` / ``shard_traverse``
    exactly like :func:`shard_pq`'s, and ``reports`` carries each shard's
    :class:`~repro.core.build.BuildReport`."""

    base_shards: jax.Array            # (P, n/P, d)
    nbr_shards: jax.Array             # (P, n/P, R)
    pq_codebooks: jax.Array | None    # (P, M, K, dsub) when compress='pq'
    pq_codes: jax.Array | None        # (P, n/P, M) uint8 when compress='pq'
    reports: tuple                    # per-shard BuildReport


def shard_build(base, n_shards: int, *, spec=None, key=None
                ) -> ShardBuildResult:
    """Per-shard build pipeline: every shard runs the SAME
    ``BuildSpec × (construct · diversify · compress)`` composition
    (``core.build``) over its local rows, under a per-shard folded key —
    sharded builds sweep the same axes as single-host builds, and a
    shard's graph/codes are bit-reproducible from (spec, key, shard id).

    ``construct='hnsw'`` is rejected: the shard bodies traverse flat
    adjacency only (the hierarchy seeder has no per-shard plumbing — seed
    shards with ``engine.shard_entries`` instead)."""
    from repro.core.build import BuildSpec, GraphBuilder

    if spec is None:
        spec = BuildSpec()
    if spec.construct == "hnsw":
        raise ValueError(
            "shard_build builds flat per-shard graphs; construct='hnsw' has "
            "no sharded search path (shard_search walks flat adjacency) — "
            "use construct='nndescent'|'exact'"
        )
    if key is None:
        key = jax.random.PRNGKey(0)
    n = base.shape[0]
    per = n // n_shards
    spec = spec._replace(graph_k=min(spec.graph_k, per - 1))
    builder = GraphBuilder(spec)
    bs, ns, cbs, codes, reports = [], [], [], [], []
    for s in range(n_shards):
        shard_base = base[s * per : (s + 1) * per]
        res = builder.build(shard_base, key=jax.random.fold_in(key, s))
        bs.append(shard_base)
        ns.append(res.graph.neighbors)
        reports.append(res.report)
        if res.pq is not None:
            cbs.append(res.pq.codebooks)
            codes.append(res.pq.codes)
    return ShardBuildResult(
        base_shards=jnp.stack(bs),
        nbr_shards=jnp.stack(ns),
        pq_codebooks=jnp.stack(cbs) if cbs else None,
        pq_codes=jnp.stack(codes) if codes else None,
        reports=tuple(reports),
    )


def shard_graph(base, neighbors, n_shards: int, *, rebuild: bool = True,
                metric: str = "l2", key=None):
    """Partition base rows into contiguous shards and produce per-shard
    graphs.

    rebuild=True (production default): each shard builds its OWN k-NN+GD
    graph over its local rows via :func:`shard_build` — masking a global
    graph would orphan most vertices (cross-shard edges dominate a random
    partition) and collapse recall; per-shard builds keep every shard
    internally navigable, which is how shard-per-machine ANN deployments
    (DiskANN-class) operate.
    rebuild=False keeps the masked-global-graph behaviour for ablation.
    Returns (base_shards (P, n/P, d), nbr_shards (P, n/P, R))."""
    n = base.shape[0]
    per = n // n_shards
    if rebuild:
        from repro.core.build import BuildSpec

        res = shard_build(
            base, n_shards,
            spec=BuildSpec(construct="nndescent", diversify="gd",
                           graph_k=20, nd_rounds=10, metric=metric,
                           proxy_sample=0),
            key=key,
        )
        return res.base_shards, res.nbr_shards
    bs, ns = [], []
    for s in range(n_shards):
        lo = s * per
        local = neighbors[lo : lo + per]
        inside = (local >= lo) & (local < lo + per)
        ns.append(jnp.where(inside, local - lo, -1))
        bs.append(base[lo : lo + per])
    return jnp.stack(bs), jnp.stack(ns)


def shard_pq(base_shards: jax.Array, M: int = 8, K: int = 256,
             iters: int = 15, key=None):
    """Per-shard PQ for the compressed scorer: each shard trains its OWN
    codebooks on its local rows (mirroring ``shard_graph``'s per-shard
    builds — a global codebook would need a training all-gather and would
    drift as shards rebalance). Returns stacked
    (codebooks (P, M, K, dsub), codes (P, n/P, M))."""
    from repro.baselines.pq import build_pq

    if key is None:
        key = jax.random.PRNGKey(0)
    cbs, codes = [], []
    for s in range(base_shards.shape[0]):
        idx = build_pq(base_shards[s], M=M, K=K, iters=iters,
                       key=jax.random.fold_in(key, s))
        cbs.append(idx.codebooks)
        codes.append(idx.codes)
    return jnp.stack(cbs), jnp.stack(codes)


def distributed_search(
    queries: jax.Array,       # (Q, d) replicated
    base_shards: jax.Array,   # (P, n/P, d) sharded on axis 0 (device tier);
                              # ignored under host/disk placements
    nbr_shards: jax.Array,    # (P, n/P, R) sharded on axis 0
    entry_ids: jax.Array,     # (P, Q, E) local entries per shard
    live_mask: jax.Array,     # (P,) bool — False = failed/straggler shard
    *,
    ef: int,
    k: int,
    metric: str = "l2",
    mesh: Mesh,
    axis: str = "shards",
    expand_width: int = 1,
    r_tile: int = 0,
    scorer: str = "exact",
    rerank: int = 0,
    pq_codebooks: jax.Array | None = None,  # (P, M, K, dsub), scorer="pq"
    pq_codes: jax.Array | None = None,      # (P, n/P, M) uint8, scorer="pq"
    base_placement: str = "device",
    host_base=None,           # (n, d) host array / BaseStore, placement="host"
):
    """Shard-and-merge search: each shard runs the SAME SearchEngine beam core
    (``engine.shard_search``); this wrapper only binds the mesh layout.

    scorer="pq" traverses each shard on its local code table (``shard_pq``):
    the ADC LUTs are built inside the shard body from the replicated queries
    and the shard's own codebooks, and the in-shard exact rerank restores
    exact distances before the cross-shard merge — so the merge compares the
    same currency as the exact path.

    base_placement="host" (DESIGN.md §9) drops the float shards from device
    memory entirely: the shard bodies traverse codes only and all-gather
    their top-``rerank`` ADC survivors (``engine.shard_traverse``), then the
    exact rerank + merge runs HERE, outside shard_map, against the one
    host-resident ``host_base`` — the merge currency is still exact
    distances, now paid for with host-gather bytes instead of per-shard HBM
    residency. base_placement="disk" (§15) is the same pipeline with the
    global base behind mmap'd shards (pass a ``BaseStore`` built via
    ``BaseStore.from_shards`` as ``host_base``, or an array to spill)."""
    if base_placement == "device":
        return _distributed_search_device(
            queries, base_shards, nbr_shards, entry_ids, live_mask,
            ef=ef, k=k, metric=metric, mesh=mesh, axis=axis,
            expand_width=expand_width, r_tile=r_tile, scorer=scorer,
            rerank=rerank, pq_codebooks=pq_codebooks, pq_codes=pq_codes,
        )
    check_placement(base_placement)
    if pq_codebooks is None or pq_codes is None:
        raise ValueError(f"base_placement={base_placement!r} traverses "
                         "per-shard code tables: pass scorer='pq' with "
                         "pq_codebooks/pq_codes (see shard_pq)")
    if host_base is None:
        raise ValueError(f"base_placement={base_placement!r} needs "
                         "host_base= (the global float base: a host array, "
                         "or a BaseStore over mmap'd shards)")
    store = BaseStore.wrap(host_base, base_placement)
    spec = SearchSpec(ef=ef, k=k, metric=metric, expand_width=expand_width,
                      r_tile=r_tile, scorer=scorer, rerank=rerank,
                      base_placement=base_placement)
    r = rerank_slice(ef, k, rerank)
    flat_i, raw_comps = _distributed_traverse(
        queries, nbr_shards, entry_ids, live_mask, pq_codebooks, pq_codes,
        spec=spec, mesh=mesh, axis=axis, r=r,
    )
    rows, _ = store.gather(flat_i)          # async host->device, (Q, P*r, d)
    md, mi = rerank_gathered(queries, flat_i, rows, k=k, metric=metric)
    M = pq_codes.shape[2]
    comps = (raw_comps * M) // store.d      # ADC hops at M/d of a comparison
    comps = comps + (flat_i >= 0).sum(axis=1, dtype=jnp.int32)
    return md, mi, comps


@functools.partial(
    jax.jit,
    static_argnames=("spec", "mesh", "axis", "r"),
)
def _distributed_traverse(queries, nbr_shards, entry_ids, live_mask,
                          pq_codebooks, pq_codes, *, spec: SearchSpec,
                          mesh: Mesh, axis: str, r: int):
    """shard_map half of the host-tier path: code-only traversal per shard,
    replicated (Q, P*r) global survivor ids + raw scored-id counts out."""
    from repro.baselines.pq import build_adc_luts

    per = nbr_shards.shape[1]

    def local(qs, nb, ent, live, cb, cd):
        luts = build_adc_luts(qs, cb[0], spec.metric)
        return engine.shard_traverse(
            qs, nb[0], ent[0], live[0], spec=spec, axis=axis, per=per, r=r,
            scorer_state=(cd[0], luts),
        )

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(), P()),
        check_vma=False,
    )(queries, nbr_shards, entry_ids, live_mask, pq_codebooks, pq_codes)


@functools.partial(
    jax.jit,
    static_argnames=("ef", "k", "metric", "mesh", "axis", "expand_width",
                     "r_tile", "scorer", "rerank"),
)
def _distributed_search_device(
    queries: jax.Array,
    base_shards: jax.Array,
    nbr_shards: jax.Array,
    entry_ids: jax.Array,
    live_mask: jax.Array,
    *,
    ef: int,
    k: int,
    metric: str = "l2",
    mesh: Mesh,
    axis: str = "shards",
    expand_width: int = 1,
    r_tile: int = 0,
    scorer: str = "exact",
    rerank: int = 0,
    pq_codebooks: jax.Array | None = None,
    pq_codes: jax.Array | None = None,
):
    per = base_shards.shape[1]
    spec = SearchSpec(ef=ef, k=k, metric=metric, expand_width=expand_width,
                      r_tile=r_tile, scorer=scorer, rerank=rerank)

    if scorer == "pq":
        if pq_codebooks is None or pq_codes is None:
            raise ValueError("scorer='pq' needs pq_codebooks/pq_codes "
                             "(see shard_pq)")
        from repro.baselines.pq import build_adc_luts

        def local(qs, b, nb, ent, live, cb, cd):
            luts = build_adc_luts(qs, cb[0], metric)
            return engine.shard_search(
                qs, b[0], nb[0], ent[0], live[0], spec=spec, axis=axis,
                per=per, scorer_state=(cd[0], luts),
            )

        return shard_map(
            local,
            mesh=mesh,
            in_specs=(P(), P(axis), P(axis), P(axis), P(axis), P(axis),
                      P(axis)),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )(queries, base_shards, nbr_shards, entry_ids, live_mask,
          pq_codebooks, pq_codes)

    def local(qs, b, nb, ent, live):
        return engine.shard_search(
            qs, b[0], nb[0], ent[0], live[0], spec=spec, axis=axis, per=per
        )

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )(queries, base_shards, nbr_shards, entry_ids, live_mask)


def distributed_build_and_search(
    base, queries, mesh: Mesh, ef: int = 64, k: int = 1,
    metric: str = "l2", key=None, graph_neighbors=None,
):
    """Convenience wrapper: build (or take) a flat graph, shard it over the
    mesh's device count, search with all shards live."""
    from repro.core.diversify import build_gd_graph
    from repro.core.nndescent import NNDescentConfig, build_knn_graph

    if key is None:
        key = jax.random.PRNGKey(0)
    n_shards = mesh.devices.size
    if graph_neighbors is None:
        g = build_knn_graph(base, NNDescentConfig(), metric=metric, key=key)
        graph_neighbors = build_gd_graph(base, g, metric=metric).neighbors
    bs, ns = shard_graph(base, graph_neighbors, n_shards)
    Q = queries.shape[0]
    ent = engine.shard_entries(key, n_shards, Q, bs.shape[1], min(8, ef))
    live = jnp.ones((n_shards,), bool)
    return distributed_search(
        queries, bs, ns, ent, live, ef=ef, k=k, metric=metric,
        mesh=mesh, axis=mesh.axis_names[0],
    )
