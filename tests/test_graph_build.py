"""NN-Descent convergence, diversification invariants, HNSW structure."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bruteforce, diversify, hnsw, nndescent
from repro.core.topk import INVALID

GOLDEN = os.path.join(os.path.dirname(__file__), "data", "golden_engine.npz")

SMALL_CFG = nndescent.NNDescentConfig(k=10, sample=10, sample_nn=10,
                                      reverse=20, rounds=12)


@pytest.fixture(scope="module")
def small_world():
    key = jax.random.PRNGKey(0)
    base = jax.random.uniform(key, (3000, 12))
    exact = bruteforce.exact_knn_graph(base, 10)
    graph, stats = nndescent.build_knn_graph_with_stats(base, SMALL_CFG,
                                                        key=key)
    return base, exact, graph, stats


def test_nndescent_recall(small_world):
    _, exact, graph, _ = small_world
    rec = nndescent.graph_recall(graph, exact)
    assert rec >= 0.90, rec


def test_nndescent_convergence_stats(small_world):
    """The convergence plumbing is truthful: one curve entry per executed
    round, strictly inside the budget when the early-termination rule fired,
    and the final update count is the one that crossed the threshold."""
    _, _, _, stats = small_world
    assert stats.rounds == len(stats.update_curve) <= SMALL_CFG.rounds
    assert stats.threshold == SMALL_CFG.delta * 3000 * SMALL_CFG.k
    if stats.converged:
        assert stats.update_curve[-1] <= stats.threshold
        assert all(u > stats.threshold for u in stats.update_curve[:-1])


def test_nndescent_early_termination_fires(small_world):
    """A loose delta must actually stop the loop early — the threshold is
    live, not decorative."""
    base, _, _, _ = small_world
    cfg = SMALL_CFG._replace(delta=0.2)
    graph, stats = nndescent.build_knn_graph_with_stats(
        base, cfg, key=jax.random.PRNGKey(0)
    )
    assert stats.converged
    assert stats.rounds < cfg.rounds, stats.update_curve
    assert stats.update_curve[-1] <= stats.threshold
    # the early stop still leaves a usable graph
    assert (np.asarray(graph.neighbors) >= 0).all()


def test_build_adjacency_matches_golden():
    """Fixed-seed golden BUILD adjacency: NN-Descent and the GD prune +
    reverse union reproduce the committed arrays bit-for-bit — silent build
    drift fails CI even when downstream search outputs absorb it.
    Regenerate via tests/data/make_golden.py ONLY on purpose.
    On a chip this golden does not apply: it pins the jnp reference
    dispatch (``JAX_PLATFORMS=cpu``), while the native kernels compute l2
    in expanded-norm form, so distances move in the low bits there (and
    ids on exact ties)."""
    gold = np.load(GOLDEN)
    key = jax.random.PRNGKey(42)
    base = jax.random.uniform(key, (2000, 16))
    g = nndescent.build_knn_graph(
        base, nndescent.NNDescentConfig(k=16, rounds=8),
        key=jax.random.PRNGKey(3),
    )
    np.testing.assert_array_equal(np.asarray(g.neighbors),
                                  gold["build_knn_ids"])
    gd = diversify.build_gd_graph(base, g)
    np.testing.assert_array_equal(np.asarray(gd.neighbors),
                                  gold["build_gd_ids"])


def test_prunes_deterministic_across_rebuilds():
    """Same key -> same NN-Descent graph -> same GD/DPG prunes, bit-for-bit
    (the reproducibility the artifact provenance and golden fixtures ride
    on)."""
    key = jax.random.PRNGKey(6)
    base = jax.random.uniform(key, (600, 8))
    runs = []
    for _ in range(2):
        g = nndescent.build_knn_graph(
            base, nndescent.NNDescentConfig(k=12, rounds=5),
            key=jax.random.PRNGKey(13),
        )
        gd = diversify.build_gd_graph(base, g)
        dpg = diversify.build_dpg_graph(base, g)
        runs.append((np.asarray(g.neighbors), np.asarray(gd.neighbors),
                     np.asarray(dpg.neighbors)))
    for a, b in zip(runs[0], runs[1]):
        np.testing.assert_array_equal(a, b)


def test_nndescent_rows_unique(small_world):
    _, _, graph, _ = small_world
    ids = np.asarray(graph.neighbors)
    for row in ids[:200]:
        row = row[row >= 0]
        assert len(set(row.tolist())) == len(row)


def test_gd_prune_subset_and_cap(small_world):
    base, _, graph, _ = small_world
    kept = diversify.gd_prune(base, graph)
    ids = np.asarray(graph.neighbors)
    kp = np.asarray(kept)
    L = graph.degree
    for r in range(100):
        k_r = kp[r][kp[r] >= 0]
        assert len(k_r) <= L // 2
        assert set(k_r.tolist()) <= set(ids[r][ids[r] >= 0].tolist())


def test_gd_occlusion_property(small_world):
    """Every kept neighbor is closer to the host than to any earlier-kept one
    (paper Fig. 2 rule)."""
    base, _, graph, _ = small_world
    kept = diversify.gd_prune(base, graph)
    b = np.asarray(base)
    kp = np.asarray(kept)
    for r in range(50):
        ks = [c for c in kp[r] if c >= 0]
        for j, c in enumerate(ks):
            d_vc = ((b[r] - b[c]) ** 2).sum()
            for s in ks[:j]:
                d_sc = ((b[s] - b[c]) ** 2).sum()
                assert d_vc < d_sc + 1e-5


def test_reverse_union_contains_forward(small_world):
    base, _, graph, _ = small_world
    kept = diversify.gd_prune(base, graph)
    merged = diversify.add_reverse_edges(kept, graph.degree)
    kp, mg = np.asarray(kept), np.asarray(merged)
    for r in range(100):
        fwd = set(kp[r][kp[r] >= 0].tolist())
        got = set(mg[r][mg[r] >= 0].tolist())
        # forward edges survive unless the degree cap evicted them
        assert len(fwd - got) == 0 or len(got) == graph.degree


def test_dpg_prune_cap(small_world):
    base, _, graph, _ = small_world
    kept = diversify.dpg_prune(base, graph)
    kp = np.asarray(kept)
    assert ((kp >= 0).sum(1) <= graph.degree // 2).all()


def test_hnsw_levels_distribution():
    cfg = hnsw.HnswConfig(M=16)
    lv = hnsw.assign_levels(jax.random.PRNGKey(1), 200_000, cfg)
    frac_l1 = float((lv >= 1).mean())
    # P(level >= 1) = exp(-ln M) = 1/M
    assert abs(frac_l1 - 1 / 16) < 0.01, frac_l1


def test_hnsw_build_and_search_small():
    key = jax.random.PRNGKey(2)
    base = jax.random.uniform(key, (3000, 8))
    queries = jax.random.uniform(jax.random.fold_in(key, 1), (50, 8))
    idx = hnsw.build_hnsw(base, hnsw.HnswConfig(M=12, knn_k=16, brute_threshold=4096))
    gt = bruteforce.ground_truth(queries, base, 1)
    res = hnsw.hnsw_search(queries, base, idx, ef=24)
    recall = float((res.ids[:, 0] == gt[:, 0]).mean())
    assert recall > 0.9, recall
    # bottom layer covers all nodes
    assert idx.layers_neighbors[0].shape[0] == 3000
    # entry point lives on the top layer
    assert int(idx.levels[idx.entry_point]) == idx.num_layers - 1
