"""SearchEngine: refactor parity (vs. pre-refactor golden outputs and inline
compositions) + recall/cost sanity per entry strategy."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bruteforce, diversify, hnsw, nndescent
from repro.core.beam_search import beam_search, random_entries
from repro.core.engine import (
    ENTRY_STRATEGIES,
    Searcher,
    SearchSpec,
    emulated_shard_search,
    merge_shard_results,
    register_entry_strategy,
    shard_entries,
)

GOLDEN = os.path.join(os.path.dirname(__file__), "data", "golden_engine.npz")


@pytest.fixture(scope="module")
def world():
    """Deterministic small world — the exact keys the golden file was
    captured with (pre-refactor seed code)."""
    key = jax.random.PRNGKey(42)
    base = jax.random.uniform(key, (2000, 16))
    queries = jax.random.uniform(jax.random.fold_in(key, 1), (32, 16))
    g = nndescent.build_knn_graph(
        base, nndescent.NNDescentConfig(k=16, rounds=8), key=jax.random.PRNGKey(3)
    )
    gd = diversify.build_gd_graph(base, g)
    idx = hnsw.build_hnsw(
        base, hnsw.HnswConfig(M=8, knn_k=16, brute_threshold=4096),
        key=jax.random.PRNGKey(5),
    )
    gt = bruteforce.ground_truth(queries, base, 1)
    return base, queries, gd, idx, gt


def test_flat_search_matches_pre_refactor_golden(world):
    """Flat-graph search reproduces the committed golden outputs.
    On a chip this golden does not apply: it pins the jnp reference
    dispatch (``JAX_PLATFORMS=cpu``), while the native kernels compute l2
    in expanded-norm form, so distances move in the low bits there (and
    ids on exact ties)."""
    base, queries, gd, idx, _ = world
    gold = np.load(GOLDEN)
    r = hnsw.flat_search(queries, base, gd, ef=32, k=4,
                         key=jax.random.PRNGKey(7), n_seeds=8)
    np.testing.assert_array_equal(np.asarray(r.ids), gold["flat_ids"])
    np.testing.assert_array_equal(np.asarray(r.dists), gold["flat_dists"])
    np.testing.assert_array_equal(np.asarray(r.n_comps), gold["flat_comps"])


def test_hnsw_search_matches_pre_refactor_golden(world):
    """Hierarchy-seeded search reproduces the committed golden outputs.
    On a chip this golden does not apply: it pins the jnp reference
    dispatch (``JAX_PLATFORMS=cpu``), while the native kernels compute l2
    in expanded-norm form, so distances move in the low bits there (and
    ids on exact ties)."""
    base, queries, gd, idx, _ = world
    gold = np.load(GOLDEN)
    r = hnsw.hnsw_search(queries, base, idx, ef=32, k=4)
    np.testing.assert_array_equal(np.asarray(r.ids), gold["hier_ids"])
    np.testing.assert_array_equal(np.asarray(r.dists), gold["hier_dists"])
    np.testing.assert_array_equal(np.asarray(r.n_comps), gold["hier_comps"])


def test_engine_random_equals_inline_composition(world):
    """flat_search == random_entries + beam_search composed by hand: the
    wrapper adds no seeding/merge logic of its own."""
    base, queries, gd, idx, _ = world
    key = jax.random.PRNGKey(13)
    via_engine = hnsw.flat_search(queries, base, gd, ef=24, k=2, key=key,
                                  n_seeds=6)
    ent = random_entries(key, base.shape[0], queries.shape[0], 6)
    inline = beam_search(queries, base, gd.neighbors, ent, ef=24, k=2)
    np.testing.assert_array_equal(np.asarray(via_engine.ids),
                                  np.asarray(inline.ids))
    np.testing.assert_array_equal(np.asarray(via_engine.n_comps),
                                  np.asarray(inline.n_comps))


@pytest.mark.parametrize("entry", ["random", "projection", "hierarchy", "lsh",
                                   "hubs"])
def test_entry_strategy_recall_and_cost(world, entry):
    """Every registered strategy reaches high recall at a fraction of the
    exhaustive comparison budget, through the one engine."""
    base, queries, gd, idx, gt = world
    searcher = Searcher.from_hnsw(base, idx)
    res = searcher.search(queries, SearchSpec(ef=48, k=1, entry=entry))
    recall = float((res.ids[:, 0] == gt[:, 0]).mean())
    comps = float(res.n_comps.mean())
    assert recall >= 0.9, (entry, recall)
    assert comps < base.shape[0], (entry, comps)  # cheaper than exhaustive
    # candidate list valid & ascending
    d = np.asarray(res.dists[:, 0])
    assert np.isfinite(d).all()


def test_recall_improves_with_ef_per_strategy(world):
    base, queries, gd, idx, gt = world
    searcher = Searcher.from_hnsw(base, idx)
    for entry in sorted(ENTRY_STRATEGIES):
        recs, comps = [], []
        for ef in (4, 16, 48):
            r = searcher.search(queries, SearchSpec(ef=ef, k=1, entry=entry))
            recs.append(float((r.ids[:, 0] == gt[:, 0]).mean()))
            comps.append(float(r.n_comps.mean()))
        assert recs[-1] >= recs[0], (entry, recs)
        assert comps[-1] > comps[0], (entry, comps)  # more ef -> more work


def test_seed_comps_accounting(world):
    """projection/lsh charge their scan to n_comps; random charges nothing
    beyond the beam's own entry evaluations."""
    base, queries, gd, idx, _ = world
    searcher = Searcher.from_hnsw(base, idx)
    n, d = base.shape
    spec = SearchSpec(ef=16, k=1, entry="projection", proj_dim=8)
    ent, extra = searcher.seed(queries, spec)
    assert ent.shape == (queries.shape[0], spec.num_seeds)
    assert int(extra[0]) == int(n * 8 / d)
    _, extra_r = searcher.seed(queries, SearchSpec(ef=16, entry="random"))
    assert int(extra_r.sum()) == 0
    _, extra_l = searcher.seed(queries, SearchSpec(ef=16, entry="lsh",
                                                   lsh_probes=32))
    assert int(extra_l[0]) == 32 + int(n * 8 / d)


def test_metric_mismatch_raises(world):
    """A spec whose metric disagrees with the index's metric must not search
    silently with wrong distances."""
    base, queries, gd, idx, _ = world
    searcher = Searcher.from_graph(base, gd, metric="ip")
    with pytest.raises(ValueError, match="metric"):
        searcher.search(queries, SearchSpec(ef=16))  # default l2 vs ip
    assert searcher.spec(ef=16).metric == "ip"


def test_hierarchy_strategy_requires_index(world):
    base, queries, gd, idx, _ = world
    flat_only = Searcher.from_graph(base, gd)
    with pytest.raises(ValueError, match="hierarchy"):
        flat_only.search(queries, SearchSpec(ef=16, entry="hierarchy"))


def test_register_custom_strategy(world):
    """The extension point: a new seeder plugs in without touching the core."""
    base, queries, gd, idx, gt = world

    class FixedEntry:
        name = "_test_fixed"

        def prepare(self, base, neighbors, hierarchy, spec, key):
            return None

        def seed(self, aux, queries, base, spec, key):
            Q = queries.shape[0]
            ent = jnp.zeros((Q, 1), jnp.int32)  # always start at vertex 0
            return ent, jnp.zeros((Q,), jnp.int32)

    register_entry_strategy(FixedEntry)
    try:
        searcher = Searcher.from_graph(base, gd)
        r = searcher.search(queries, SearchSpec(ef=48, entry="_test_fixed"))
        assert float((r.ids[:, 0] == gt[:, 0]).mean()) > 0.8
    finally:
        del ENTRY_STRATEGIES["_test_fixed"]


def test_emulated_shard_search_matches_manual_merge(world):
    """The engine's shard plumbing == per-shard beam + top-k merge by hand
    (the pre-refactor distributed_search local body)."""
    base, queries, gd, idx, gt = world
    n_shards, per = 4, base.shape[0] // 4
    bs = jnp.stack([base[s * per:(s + 1) * per] for s in range(n_shards)])
    # mask the global graph to local targets (rebuild=False layout)
    ns = []
    for s in range(n_shards):
        local = gd.neighbors[s * per:(s + 1) * per]
        inside = (local >= s * per) & (local < (s + 1) * per)
        ns.append(jnp.where(inside, local - s * per, -1))
    ns = jnp.stack(ns)
    ent = shard_entries(jax.random.PRNGKey(11), n_shards, queries.shape[0],
                        per, 8)
    live = jnp.ones((n_shards,), bool).at[2].set(False)
    spec = SearchSpec(ef=32, k=2)

    d_eng, i_eng = emulated_shard_search(queries, bs, ns, ent, live, spec)

    all_d, all_i = [], []
    for s in range(n_shards):
        res = beam_search(queries, bs[s], ns[s], ent[s], ef=32, k=2)
        gids = jnp.where(res.ids >= 0, res.ids + s * per, -1)
        all_d.append(jnp.where(live[s], res.dists, jnp.inf))
        all_i.append(jnp.where(live[s], gids, -1))
    d_man, i_man = merge_shard_results(
        jnp.concatenate(all_d, 1), jnp.concatenate(all_i, 1), 2
    )
    np.testing.assert_array_equal(np.asarray(i_eng), np.asarray(i_man))
    np.testing.assert_allclose(np.asarray(d_eng), np.asarray(d_man))


def test_expand_width_through_engine(world):
    """expand_width reaches the beam core from the spec (wide-expansion fast
    path for every caller)."""
    base, queries, gd, idx, gt = world
    searcher = Searcher.from_graph(base, gd)
    r1 = searcher.search(queries, SearchSpec(ef=32, entry="random"))
    r4 = searcher.search(queries, SearchSpec(ef=32, entry="random",
                                             expand_width=4))
    assert int(r4.n_steps) < int(r1.n_steps)
    rec1 = float((r1.ids[:, 0] == gt[:, 0]).mean())
    rec4 = float((r4.ids[:, 0] == gt[:, 0]).mean())
    assert rec4 >= rec1 - 0.05


def test_search_stream_matches_monolithic(world):
    """Streaming tiles through a key-deterministic seeder must return exactly
    what one monolithic batch would — tiling is a throughput choice, not a
    semantic one."""
    base, queries, gd, idx, _ = world
    searcher = Searcher.from_graph(base, gd)
    spec = SearchSpec(ef=32, k=2, entry="projection")
    mono = searcher.search(queries, spec)
    # tile_q=10 forces ragged last-tile padding (32 = 3*10 + 2)
    stream = searcher.search_stream(queries, spec, tile_q=10)
    np.testing.assert_array_equal(np.asarray(mono.ids),
                                  np.asarray(stream.ids))
    np.testing.assert_array_equal(np.asarray(mono.dists),
                                  np.asarray(stream.dists))
    np.testing.assert_array_equal(np.asarray(mono.n_comps),
                                  np.asarray(stream.n_comps))


def test_search_stream_random_strategy_recall(world):
    """Per-tile seed keys: the random strategy streams with fresh draws per
    tile and still reaches monolithic-grade recall."""
    base, queries, gd, idx, gt = world
    searcher = Searcher.from_graph(base, gd)
    spec = SearchSpec(ef=48, k=1, entry="random")
    res = searcher.search_stream(queries, spec, tile_q=8)
    assert res.ids.shape == (queries.shape[0], 1)
    assert float((res.ids[:, 0] == gt[:, 0]).mean()) >= 0.9


def test_r_tile_spec_is_result_invariant(world):
    """r_tile only re-tiles the gather kernel; results cannot move."""
    base, queries, gd, idx, _ = world
    searcher = Searcher.from_hnsw(base, idx)
    r_def = searcher.search(queries, SearchSpec(ef=32, entry="hierarchy"))
    r_t4 = searcher.search(queries, SearchSpec(ef=32, entry="hierarchy",
                                               r_tile=4))
    np.testing.assert_array_equal(np.asarray(r_def.ids), np.asarray(r_t4.ids))
    np.testing.assert_array_equal(np.asarray(r_def.n_comps),
                                  np.asarray(r_t4.n_comps))


PQ_TEST_SPEC = dict(scorer="pq", pq_m=8, pq_k=64)


@pytest.mark.parametrize("entry", ["random", "projection", "hierarchy", "lsh",
                                   "hubs"])
def test_pq_scorer_recall_per_strategy(world, entry):
    """The scorer axis is orthogonal to the entry axis: pq-scored traversal
    with exact rerank reaches >= 0.95 of the exact-scored recall at equal ef
    for EVERY registered seeder, and its comps stay cheaper (ADC charged at
    M/d plus the rerank)."""
    base, queries, gd, idx, gt = world
    searcher = Searcher.from_hnsw(base, idx)
    ex = searcher.search(queries, SearchSpec(ef=48, k=1, entry=entry))
    pq = searcher.search(
        queries, SearchSpec(ef=48, k=1, entry=entry, **PQ_TEST_SPEC)
    )
    rec_ex = float((ex.ids[:, 0] == gt[:, 0]).mean())
    rec_pq = float((pq.ids[:, 0] == gt[:, 0]).mean())
    assert rec_pq >= 0.95 * rec_ex, (entry, rec_ex, rec_pq)
    # rerank restored exact distances: reported dists match the base metric
    nn = np.asarray(base)[np.asarray(pq.ids[:, 0])]
    d0 = ((np.asarray(queries) - nn) ** 2).sum(-1)
    np.testing.assert_allclose(np.asarray(pq.dists[:, 0]), d0, rtol=1e-5,
                               atol=1e-5)


def test_pq_scorer_comps_accounting(world):
    """ADC hops are charged at M/d of a full comparison plus one full
    comparison per reranked survivor — the pq traversal must come in under
    the exact traversal's bill once seeds are equal."""
    base, queries, gd, idx, gt = world
    searcher = Searcher.from_hnsw(base, idx)
    spec_ex = SearchSpec(ef=48, k=1, entry="random")
    ent, extra = searcher.seed(queries, spec_ex)
    ex = searcher.search(queries, spec_ex, entries=ent, entry_comps=extra)
    pq = searcher.search(
        queries, SearchSpec(ef=48, k=1, entry="random", **PQ_TEST_SPEC),
        entries=ent, entry_comps=extra,
    )
    assert float(pq.n_comps.mean()) < float(ex.n_comps.mean())
    # rerank budget caps the exact tail: fewer reranked -> fewer comps
    pq16 = searcher.search(
        queries, SearchSpec(ef=48, k=1, entry="random", rerank=16,
                            **PQ_TEST_SPEC),
        entries=ent, entry_comps=extra,
    )
    assert float(pq16.n_comps.mean()) < float(pq.n_comps.mean())


def test_search_stream_matches_monolithic_pq(world):
    """Streaming under scorer='pq' bit-matches the monolithic batch: per-tile
    LUT builds and the shared code table are deterministic, so tiling stays a
    throughput choice under the compressed scorer too."""
    base, queries, gd, idx, _ = world
    searcher = Searcher.from_graph(base, gd)
    spec = SearchSpec(ef=32, k=2, entry="projection", **PQ_TEST_SPEC)
    mono = searcher.search(queries, spec)
    stream = searcher.search_stream(queries, spec, tile_q=10)
    np.testing.assert_array_equal(np.asarray(mono.ids),
                                  np.asarray(stream.ids))
    np.testing.assert_array_equal(np.asarray(mono.dists),
                                  np.asarray(stream.dists))
    np.testing.assert_array_equal(np.asarray(mono.n_comps),
                                  np.asarray(stream.n_comps))


# -- sq8: the scalar-quantized middle rung of the ladder (DESIGN.md §15) ------


def test_sq8_recall_sandwich(world):
    """The ladder's ordering at equal ef and shared seeds: sq8 traversal
    (full-rank geometry, d bytes/vertex) recalls at least as well as pq
    (M bytes/vertex) within slack and at most exact, while its scored-base
    traffic sits ~4x below the exact scorer's 4d bytes/vertex."""
    base, queries, gd, idx, gt = world
    searcher = Searcher.from_hnsw(base, idx)
    spec = SearchSpec(ef=48, k=1, entry="projection")
    ent, extra = searcher.seed(queries, spec)
    specs = {
        "exact": spec,
        "sq8": spec._replace(scorer="sq8"),
        "pq": spec._replace(**PQ_TEST_SPEC),
    }
    runs = {
        sc: searcher.search(queries, s, entries=ent, entry_comps=extra)
        for sc, s in specs.items()
    }
    rec = {sc: float((r.ids[:, 0] == gt[:, 0]).mean())
           for sc, r in runs.items()}
    assert rec["pq"] - 0.02 <= rec["sq8"] <= rec["exact"] + 0.02, rec
    # scored share: sq8 bills d bytes/vertex vs exact's 4d. Back the rerank
    # rows (all ef survivors at 4d each, rerank=0) out of the sq8 bill; the
    # traversals differ slightly so gate the 4x at a 3x floor on means.
    d = base.shape[1]
    sq8_scored = np.asarray(runs["sq8"].bytes_touched) - 48 * d * 4
    assert (sq8_scored > 0).all()
    assert sq8_scored.mean() * 3.0 < np.asarray(
        runs["exact"].bytes_touched).mean()
    # rerank restored exact distances
    nn = np.asarray(base)[np.asarray(runs["sq8"].ids[:, 0])]
    d0 = ((np.asarray(queries) - nn) ** 2).sum(-1)
    np.testing.assert_allclose(np.asarray(runs["sq8"].dists[:, 0]), d0,
                               rtol=1e-5, atol=1e-5)


def test_search_stream_matches_monolithic_sq8(world):
    """Streaming under scorer='sq8' bit-matches the monolithic batch — the
    shared uint8 table and per-dim dequant params are deterministic, so
    tiling stays a throughput choice on the middle rung too."""
    base, queries, gd, idx, _ = world
    searcher = Searcher.from_graph(base, gd)
    spec = SearchSpec(ef=32, k=2, entry="projection", scorer="sq8")
    mono = searcher.search(queries, spec)
    stream = searcher.search_stream(queries, spec, tile_q=10)
    np.testing.assert_array_equal(np.asarray(mono.ids),
                                  np.asarray(stream.ids))
    np.testing.assert_array_equal(np.asarray(mono.dists),
                                  np.asarray(stream.dists))
    np.testing.assert_array_equal(np.asarray(mono.n_comps),
                                  np.asarray(stream.n_comps))
    np.testing.assert_array_equal(np.asarray(mono.bytes_touched),
                                  np.asarray(stream.bytes_touched))


def test_sq8_index_is_lazy_and_cached(world):
    """The uint8 table trains once per searcher (deterministic min/max scan)
    and is reused across searches — same object, same results."""
    base, queries, gd, idx, _ = world
    searcher = Searcher.from_graph(base, gd, key=jax.random.PRNGKey(7))
    spec = SearchSpec(ef=32, k=2, entry="projection", scorer="sq8")
    a = searcher.search(queries, spec)
    first = searcher.sq8_index()
    b = searcher.search(queries, spec)
    assert searcher.sq8_index() is first
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.dists), np.asarray(b.dists))


def test_pq_search_matches_golden(world):
    """Determinism lock: a freshly trained PQ engine (k-means re-seeding
    folds the iteration index) reproduces the committed pq_* outputs
    bit-for-bit — regenerate via tests/data/make_golden.py ONLY on purpose.
    On a chip this golden does not apply: it pins the jnp reference
    dispatch (``JAX_PLATFORMS=cpu``), while the native kernels compute l2
    in expanded-norm form, so distances move in the low bits there (and
    ids on exact ties)."""
    base, queries, gd, idx, _ = world
    gold = np.load(GOLDEN)
    searcher = Searcher.from_graph(base, gd, key=jax.random.PRNGKey(7))
    res = searcher.search(
        queries,
        SearchSpec(ef=32, k=4, entry="projection", **PQ_TEST_SPEC),
    )
    np.testing.assert_array_equal(np.asarray(res.ids), gold["pq_ids"])
    np.testing.assert_array_equal(np.asarray(res.dists), gold["pq_dists"])
    np.testing.assert_array_equal(np.asarray(res.n_comps), gold["pq_comps"])


def test_host_placement_matches_golden(world):
    """base_placement='host' reruns the golden pq world off a host-resident
    base and must land on the committed pq_* outputs bit-for-bit — the
    tiered rerank is the same survivors, same distance formula, same bill
    (DESIGN.md §9). On a chip this golden does not apply: it pins the jnp reference
    dispatch (``JAX_PLATFORMS=cpu``), while the native kernels compute l2
    in expanded-norm form, so distances move in the low bits there (and
    ids on exact ties)."""
    base, queries, gd, idx, _ = world
    gold = np.load(GOLDEN)
    searcher = Searcher.from_graph(base, gd, key=jax.random.PRNGKey(7))
    res = searcher.search(
        queries,
        SearchSpec(ef=32, k=4, entry="projection", base_placement="host",
                   **PQ_TEST_SPEC),
    )
    np.testing.assert_array_equal(np.asarray(res.ids), gold["pq_ids"])
    np.testing.assert_array_equal(np.asarray(res.dists), gold["pq_dists"])
    np.testing.assert_array_equal(np.asarray(res.n_comps), gold["pq_comps"])
    assert int(res.host_bytes.min()) > 0


def test_unknown_scorer_raises(world):
    base, queries, gd, idx, _ = world
    searcher = Searcher.from_graph(base, gd)
    with pytest.raises(ValueError, match="scorer"):
        searcher.search(queries, SearchSpec(ef=16, scorer="nope"))


def test_emulated_shard_search_pq(world):
    """Per-shard PQ through the emulated shard loop: each shard traverses on
    its own code table and reranks exactly before the merge, so the merged
    answer stays in exact-distance currency and recall tracks the exact
    sharded run."""
    from repro.baselines.pq import build_adc_luts
    from repro.distributed.sharded_ann import shard_pq

    base, queries, gd, idx, gt = world
    n_shards, per = 4, base.shape[0] // 4
    bs = jnp.stack([base[s * per:(s + 1) * per] for s in range(n_shards)])
    ns = []
    for s in range(n_shards):
        local = gd.neighbors[s * per:(s + 1) * per]
        inside = (local >= s * per) & (local < (s + 1) * per)
        ns.append(jnp.where(inside, local - s * per, -1))
    ns = jnp.stack(ns)
    ent = shard_entries(jax.random.PRNGKey(11), n_shards, queries.shape[0],
                        per, 8)
    live = jnp.ones((n_shards,), bool)
    cbs, codes = shard_pq(bs, M=8, K=64, key=jax.random.PRNGKey(21))
    states = [
        (codes[s], build_adc_luts(queries, cbs[s], "l2"))
        for s in range(n_shards)
    ]
    spec = SearchSpec(ef=32, k=1, **PQ_TEST_SPEC)
    d_pq, i_pq = emulated_shard_search(queries, bs, ns, ent, live, spec,
                                       scorer_states=states)
    d_ex, i_ex = emulated_shard_search(queries, bs, ns, ent, live,
                                       SearchSpec(ef=32, k=1))
    rec_ex = float((i_ex[:, 0] == gt[:, 0]).mean())
    rec_pq = float((i_pq[:, 0] == gt[:, 0]).mean())
    assert rec_pq >= 0.9 * rec_ex, (rec_ex, rec_pq)
    # merged distances are exact for the ids both runs agree on
    agree = np.asarray(i_pq[:, 0]) == np.asarray(i_ex[:, 0])
    np.testing.assert_allclose(np.asarray(d_pq[:, 0])[agree],
                               np.asarray(d_ex[:, 0])[agree], rtol=1e-5)


def test_trace_includes_seed_cost(world):
    base, queries, gd, idx, _ = world
    searcher = Searcher.from_hnsw(base, idx)
    spec = SearchSpec(ef=16, entry="projection")
    res, td, tc = searcher.search_with_trace(queries, spec, max_steps=24)
    _, extra = searcher.seed(queries, spec)
    assert (np.asarray(tc[0]) >= np.asarray(extra)).all()
    assert (np.diff(np.asarray(tc), axis=0) >= 0).all()


# -- hub seeding + per-query adaptive termination (DESIGN.md §12) -------------


def test_hubs_seed_comps_accounting(world):
    """The hub seeder charges exactly hub_count full comparisons per query
    (the exact scan over the shortlist) and returns num_seeds entries."""
    base, queries, gd, idx, _ = world
    searcher = Searcher.from_graph(base, gd)
    spec = SearchSpec(ef=16, k=1, entry="hubs", hub_count=24)
    ent, extra = searcher.seed(queries, spec)
    assert ent.shape == (queries.shape[0], spec.num_seeds)
    assert (np.asarray(extra) == 24).all()
    # seeds really are drawn from the hub shortlist
    from repro.core.graph_index import hub_vertices

    hubs = set(np.asarray(hub_vertices(gd.neighbors, 24)).tolist())
    assert set(np.asarray(ent).ravel().tolist()) <= hubs


def test_hubs_attached_matches_recompute(world):
    """A searcher carrying a persisted hub shortlist searches bit-identically
    to one that recomputes it from the adjacency — the legacy-artifact
    fallback cannot drift."""
    from repro.core.graph_index import hub_vertices

    base, queries, gd, idx, _ = world
    spec = SearchSpec(ef=32, k=2, entry="hubs")
    fresh = Searcher.from_graph(base, gd)           # recomputes on prepare
    attached = Searcher(base, gd.neighbors,
                        hubs=hub_vertices(gd.neighbors, 64))
    a = fresh.search(queries, spec)
    b = attached.search(queries, spec)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.n_comps),
                                  np.asarray(b.n_comps))


def test_stable_with_large_patience_equals_fixed(world):
    """term="stable" degenerates to term="fixed" bit-for-bit when the
    patience window can never elapse — the adaptive path adds bookkeeping,
    not behavior, until a row actually freezes."""
    base, queries, gd, idx, _ = world
    searcher = Searcher.from_graph(base, gd)
    spec_f = SearchSpec(ef=32, k=2, entry="projection")
    spec_s = SearchSpec(ef=32, k=2, entry="projection", term="stable",
                        stable_steps=10**6)
    a = searcher.search(queries, spec_f)
    b = searcher.search(queries, spec_s)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.dists), np.asarray(b.dists))
    np.testing.assert_array_equal(np.asarray(a.n_comps),
                                  np.asarray(b.n_comps))


def test_frozen_rows_stop_accruing_comps(world):
    """The §12 cost contract: same seeds, same ef — a stable run's per-row
    bill never exceeds the fixed run's, and once a row's cumulative counter
    stops moving for a full patience window it never moves again (the freeze
    is final, enforced by the done mask, not by luck)."""
    base, queries, gd, idx, _ = world
    searcher = Searcher.from_graph(base, gd)
    spec_f = SearchSpec(ef=48, k=1, entry="projection")
    spec_s = SearchSpec(ef=48, k=1, entry="projection", term="stable",
                        stable_steps=3)
    ent, extra = searcher.seed(queries, spec_f)
    fixed = searcher.search(queries, spec_f, entries=ent, entry_comps=extra)
    stable = searcher.search(queries, spec_s, entries=ent, entry_comps=extra)
    assert (np.asarray(stable.n_comps) <= np.asarray(fixed.n_comps)).all()
    assert float(stable.n_comps.mean()) < float(fixed.n_comps.mean())

    _, _, tc = searcher.search_with_trace(queries, spec_s, max_steps=80)
    tc = np.asarray(tc)
    W = spec_s.stable_steps + 2
    for q in range(tc.shape[1]):
        col = tc[:, q]
        frozen_at = next(
            (t for t in range(len(col) - W) if col[t] == col[t + W]), None
        )
        assert frozen_at is not None, f"row {q} never froze in 80 steps"
        assert (col[frozen_at:] == col[frozen_at]).all(), (
            f"row {q} accrued comparisons after its freeze"
        )


def test_stable_recall_at_matched_comps_ceiling(world):
    """The trade the sweep ships: per-query termination with a RAISED ef
    ceiling reaches at least the recall of every fixed run that spends no
    more comparisons — the saved steps were waste, not recall."""
    base, queries, gd, idx, gt = world
    searcher = Searcher.from_hnsw(base, idx)
    spec_s = SearchSpec(ef=96, k=1, entry="hierarchy", term="stable",
                        stable_steps=12)
    st = searcher.search(queries, spec_s)
    st_rec = float((st.ids[:, 0] == gt[:, 0]).mean())
    st_comps = float(st.n_comps.mean())
    for ef in (8, 16, 24, 32, 48):
        fx = searcher.search(queries, SearchSpec(ef=ef, k=1,
                                                 entry="hierarchy"))
        if float(fx.n_comps.mean()) <= st_comps:
            fx_rec = float((fx.ids[:, 0] == gt[:, 0]).mean())
            assert st_rec >= fx_rec - 0.02, (
                ef, fx_rec, st_rec, st_comps, float(fx.n_comps.mean())
            )


def test_restarts_deterministic_and_monotone(world):
    """Restarts replay bit-identically under a fixed key, only ever improve
    the answer (fresh seeds merge into the candidate list), and charge their
    extra scoring to n_comps."""
    base, queries, gd, idx, gt = world
    searcher = Searcher.from_graph(base, gd)
    key = jax.random.PRNGKey(77)
    spec_r = SearchSpec(ef=32, k=1, entry="random", term="stable",
                        stable_steps=3, restarts=2)
    a = searcher.search(queries, spec_r, key)
    b = searcher.search(queries, spec_r, key)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.dists), np.asarray(b.dists))
    np.testing.assert_array_equal(np.asarray(a.n_comps),
                                  np.asarray(b.n_comps))
    base_run = searcher.search(queries, spec_r._replace(restarts=0), key)
    assert (np.asarray(a.n_comps) >= np.asarray(base_run.n_comps)).all()
    assert float(a.n_comps.mean()) > float(base_run.n_comps.mean())
    assert (np.asarray(a.dists[:, 0]) <= np.asarray(base_run.dists[:, 0])).all()
    rec_r = float((a.ids[:, 0] == gt[:, 0]).mean())
    rec_0 = float((base_run.ids[:, 0] == gt[:, 0]).mean())
    assert rec_r >= rec_0


def test_invalid_termination_spec_raises(world):
    base, queries, gd, idx, _ = world
    searcher = Searcher.from_graph(base, gd)
    with pytest.raises(ValueError, match="term"):
        searcher.search(queries, SearchSpec(ef=16, term="bogus"))
    from repro.core.beam_search import check_termination

    with pytest.raises(ValueError, match="restart_keys"):
        check_termination("stable", 2, None)
