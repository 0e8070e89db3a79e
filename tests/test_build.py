"""Unified build pipeline: BuildSpec × (construct · diversify · compress) —
stage registries, legacy-parity, report accounting, sharded builds."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bruteforce, diversify, nndescent
from repro.core.build import (
    BuildSpec,
    GraphBuilder,
    build_index,
    graph_recall_proxy,
)
from repro.core.engine import Searcher, SearchSpec, shard_entries


@pytest.fixture(scope="module")
def world():
    key = jax.random.PRNGKey(9)
    base = jax.random.uniform(key, (900, 16))
    queries = jax.random.uniform(jax.random.fold_in(key, 1), (24, 16))
    gt = bruteforce.ground_truth(queries, base, 1)
    return base, queries, gt


# -- legacy parity: the refactor must not move a single edge ------------------


def test_flat_build_matches_pre_pipeline_composition(world):
    """Searcher.build (now on GraphBuilder) == the inline NN-Descent + GD
    composition it replaced, bit-for-bit. Both sides run the same kernels, so this holds on a chip as well."""
    base, _, _ = world
    key = jax.random.PRNGKey(3)
    g = nndescent.build_knn_graph(
        base, nndescent.NNDescentConfig(k=12), key=key
    )
    gd = diversify.build_gd_graph(base, g)
    s = Searcher.build(base, key=key, graph_k=12)
    np.testing.assert_array_equal(np.asarray(s.neighbors),
                                  np.asarray(gd.neighbors))
    assert s.build_report is not None
    assert s.build_report.spec.construct == "nndescent"


def test_hierarchy_build_matches_pre_pipeline_composition(world):
    """with_hierarchy=True == the inline NN-Descent + build_hnsw flow."""
    from repro.core import hnsw

    base, _, _ = world
    key = jax.random.PRNGKey(5)
    g = nndescent.build_knn_graph(
        base, nndescent.NNDescentConfig(k=12), key=key
    )
    idx = hnsw.build_hnsw(
        base, hnsw.HnswConfig(M=max(8, 12 // 2), knn_k=12),
        key=key, bottom_graph=g,
    )
    s = Searcher.build(base, key=key, graph_k=12, with_hierarchy=True)
    assert s.hierarchy is not None
    assert s.hierarchy.num_layers == idx.num_layers
    for a, b in zip(s.hierarchy.layers_neighbors, idx.layers_neighbors):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(s.hierarchy.entry_point) == int(idx.entry_point)


def test_compress_stage_matches_lazy_pq(world):
    """Build-time PQ uses the engine's lazy-path key derivation: the
    attached table == what a fresh engine with the same key trains on first
    use (round-tripping an artifact can never flip a search result)."""
    base, _, _ = world
    key = jax.random.PRNGKey(7)
    res = build_index(
        base,
        BuildSpec(construct="exact", diversify="gd", compress="pq",
                  graph_k=10, pq_m=8, pq_k=32),
        key=key,
    )
    lazy = Searcher(base, res.graph.neighbors, key=key)
    idx = lazy.pq_index(SearchSpec(pq_m=8, pq_k=32))
    np.testing.assert_array_equal(np.asarray(res.pq.codebooks),
                                  np.asarray(idx.codebooks))
    np.testing.assert_array_equal(np.asarray(res.pq.codes),
                                  np.asarray(idx.codes))


# -- validation ---------------------------------------------------------------


def test_unknown_stage_names_fail_before_building(world):
    with pytest.raises(ValueError, match="construct"):
        GraphBuilder(BuildSpec(construct="nope"))
    with pytest.raises(ValueError, match="diversify"):
        GraphBuilder(BuildSpec(diversify="nope"))
    with pytest.raises(ValueError, match="compress"):
        GraphBuilder(BuildSpec(compress="nope"))
    with pytest.raises(ValueError, match="reverse"):
        GraphBuilder(BuildSpec(reverse="nope"))


def test_hnsw_construct_rejects_second_diversify(world):
    with pytest.raises(ValueError, match="hnsw"):
        GraphBuilder(BuildSpec(construct="hnsw", diversify="gd"))


def test_pq_dimension_mismatch_fails_loudly(world):
    base, _, _ = world  # d=16
    with pytest.raises(ValueError, match="pq_m"):
        GraphBuilder(BuildSpec(construct="exact", compress="pq",
                               pq_m=5)).build(base)


# -- report accounting --------------------------------------------------------


def test_report_exact_construct_is_oracle(world):
    """exact + none: the constructed graph IS the true k-NN graph, so the
    recall proxy is 1.0, nothing is dropped, and degrees equal graph_k."""
    base, _, _ = world
    res = build_index(base, BuildSpec(construct="exact", diversify="none",
                                      graph_k=12))
    rep = res.report
    assert rep.graph_recall_proxy == 1.0
    assert rep.rounds == 0 and rep.converged
    assert rep.dropped_reverse_edges == 0
    assert rep.degree["min"] == rep.degree["max"] == 12
    assert rep.memory_bytes == res.graph.neighbors.size * 4
    assert rep.wall_total_s >= 0


def test_report_degree_and_dropped_consistency(world):
    """The report's degree distribution and dropped-edge count must agree
    with the adjacency it describes and with the stats-returning reverse
    union run by hand."""
    base, _, _ = world
    spec = BuildSpec(construct="exact", diversify="gd", graph_k=12)
    res = build_index(base, spec)
    rep = res.report
    deg = np.asarray((res.graph.neighbors >= 0).sum(1))
    assert rep.degree["min"] == deg.min()
    assert rep.degree["max"] == deg.max() <= 12
    assert rep.degree["hist"][deg.max()] == int((deg == deg.max()).sum())
    kept = diversify.gd_prune(base, bruteforce.exact_knn_graph(base, 12))
    merged, rstats = diversify.add_reverse_edges_with_stats(kept, 12)
    np.testing.assert_array_equal(np.asarray(res.graph.neighbors),
                                  np.asarray(merged))
    assert rep.dropped_reverse_edges == rstats.dropped


def test_reverse_policy_none_skips_union(world):
    """reverse='none': the diversified graph is the pruned survivors only —
    every edge comes from the prune, degree stays <= max_keep."""
    base, _, _ = world
    res = build_index(base, BuildSpec(construct="exact", diversify="gd",
                                      graph_k=12, reverse="none"))
    kept = diversify.gd_prune(base, bruteforce.exact_knn_graph(base, 12))
    kp, got = np.asarray(kept), np.asarray(res.graph.neighbors)
    assert ((got >= 0).sum(1) <= 6).all()  # max_keep default L/2
    for r in range(0, 900, 37):
        assert set(got[r][got[r] >= 0]) <= set(kp[r][kp[r] >= 0])
    assert res.report.dropped_reverse_edges == 0


def test_add_reverse_edges_stats_match_numpy_recount():
    """ReverseUnionStats vs a from-scratch numpy recount of the same
    deterministic slot policy (incoming edges ranked by source id, r slots
    per target, unique-id union capped at max_degree)."""
    rng = np.random.default_rng(0)
    n, r, cap = 40, 6, 8
    nbrs = rng.integers(0, n, size=(n, r)).astype(np.int32)
    nbrs[np.arange(n)[:, None] == nbrs] = -1       # no self loops
    nbrs[rng.random((n, r)) < 0.15] = -1           # some padding
    merged, stats = diversify.add_reverse_edges_with_stats(
        jnp.asarray(nbrs), cap
    )
    merged = np.asarray(merged)

    incoming: dict[int, list[int]] = {t: [] for t in range(n)}
    candidates = 0
    for s in range(n):
        for t in nbrs[s]:
            if t >= 0:
                candidates += 1
                incoming[int(t)].append(s)  # already (src, col) ordered
    kept_slot = sum(min(len(v), r) for v in incoming.values())
    dropped_cap = 0
    for v in range(n):
        fwd = {int(t) for t in nbrs[v] if t >= 0}
        rev = set(incoming[v][:r])
        union = sorted(fwd | rev)
        dropped_cap += max(0, len(union) - cap)
        got = [int(x) for x in merged[v] if x >= 0]
        assert got == union[:cap], v
    assert stats.candidates == candidates
    assert stats.dropped_slot == candidates - kept_slot
    assert stats.dropped_cap == dropped_cap


def test_reverse_none_counts_cap_truncation(world):
    """A tight max_degree under reverse='none' drops kept edges at the
    pad_neighbors cap — the report must count them like the union path
    counts its cap evictions (nothing is dropped silently)."""
    base, _, _ = world
    res = build_index(base, BuildSpec(construct="exact", diversify="gd",
                                      graph_k=12, reverse="none",
                                      max_degree=3))
    kept = diversify.gd_prune(base, bruteforce.exact_knn_graph(base, 12))
    overflow = int((np.asarray(kept)[:, 3:] >= 0).sum())
    assert overflow > 0  # the cap binds on this world
    assert res.report.dropped_reverse_edges == overflow
    assert ((np.asarray(res.graph.neighbors) >= 0).sum(1) <= 3).all()


def test_hnsw_proxy_measures_raw_graph(world):
    """The build_sweep proxy column must compare like with like: the hnsw
    row scores its RAW NN-Descent graph, not the occlusion-pruned bottom
    layer — identical quantity to the flat constructs."""
    base, _, _ = world
    key = jax.random.PRNGKey(4)
    spec = BuildSpec(construct="hnsw", diversify="none", graph_k=12,
                     nd_rounds=6)
    res = build_index(base, spec, key=key)
    g = nndescent.build_knn_graph(
        base,
        nndescent.NNDescentConfig(k=12, rounds=6),
        key=key,
    )
    want = graph_recall_proxy(base, g)
    assert res.report.graph_recall_proxy == round(want, 4)


def test_graph_recall_proxy_detects_bad_graph(world):
    """The proxy must separate a true k-NN graph (1.0) from a random one
    (~0) — the signal the build gate rides on."""
    base, _, _ = world
    good = bruteforce.exact_knn_graph(base, 10)
    assert graph_recall_proxy(base, good) == 1.0
    bad_ids = jax.random.randint(jax.random.PRNGKey(0), (900, 10), 0, 900)
    bad = good._replace(neighbors=bad_ids.astype(jnp.int32))
    assert graph_recall_proxy(base, bad) < 0.2


# -- sharded builds -----------------------------------------------------------


def test_shard_build_feeds_existing_search_paths(world):
    """shard_build output drops into emulated_shard_search (exact and pq)
    unchanged — the per-shard pipeline replaces shard_graph+shard_pq with
    one spec."""
    from repro.baselines.pq import build_adc_luts
    from repro.core.engine import emulated_shard_search
    from repro.distributed.sharded_ann import shard_build

    base, queries, gt = world
    P = 3
    res = shard_build(
        base, P,
        spec=BuildSpec(construct="exact", diversify="gd", compress="pq",
                       graph_k=10, pq_m=8, pq_k=32, proxy_sample=0),
        key=jax.random.PRNGKey(11),
    )
    per = base.shape[0] // P
    assert res.base_shards.shape == (P, per, 16)
    assert res.nbr_shards.shape[0] == P and res.nbr_shards.shape[1] == per
    assert res.pq_codes.shape == (P, per, 8)
    assert len(res.reports) == P
    assert all(r.spec.graph_k == 10 for r in res.reports)
    # local ids only
    assert int(res.nbr_shards.max()) < per
    ent = shard_entries(jax.random.PRNGKey(12), P, queries.shape[0], per, 8)
    live = jnp.ones((P,), bool)
    d_ex, i_ex = emulated_shard_search(
        queries, res.base_shards, res.nbr_shards, ent, live,
        SearchSpec(ef=32, k=1),
    )
    assert float((i_ex[:, 0] == gt[:, 0]).mean()) >= 0.8
    states = [
        (res.pq_codes[s], build_adc_luts(queries, res.pq_codebooks[s], "l2"))
        for s in range(P)
    ]
    d_pq, i_pq = emulated_shard_search(
        queries, res.base_shards, res.nbr_shards, ent, live,
        SearchSpec(ef=32, k=1, scorer="pq", pq_m=8, pq_k=32),
        scorer_states=states,
    )
    rec_ex = float((i_ex[:, 0] == gt[:, 0]).mean())
    rec_pq = float((i_pq[:, 0] == gt[:, 0]).mean())
    assert rec_pq >= 0.85 * rec_ex, (rec_ex, rec_pq)


def test_shard_build_rejects_hierarchy(world):
    from repro.distributed.sharded_ann import shard_build

    base, _, _ = world
    with pytest.raises(ValueError, match="hnsw"):
        shard_build(base, 2, spec=BuildSpec(construct="hnsw",
                                            diversify="none"))


def test_shard_build_is_deterministic(world):
    """Same (spec, key) -> bit-identical per-shard graphs (the rebuild
    reproducibility sharded deployments rely on). Both sides run the same kernels, so this holds on a chip as well."""
    from repro.distributed.sharded_ann import shard_build

    base, _, _ = world
    spec = BuildSpec(construct="nndescent", diversify="dpg", graph_k=8,
                     nd_rounds=4, proxy_sample=0)
    a = shard_build(base, 2, spec=spec, key=jax.random.PRNGKey(2))
    b = shard_build(base, 2, spec=spec, key=jax.random.PRNGKey(2))
    np.testing.assert_array_equal(np.asarray(a.nbr_shards),
                                  np.asarray(b.nbr_shards))
