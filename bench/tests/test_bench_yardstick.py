"""The benchmark's own arithmetic: reference, generators, cache key, cell
lookup and peaks."""
import json
import os

import numpy as np
import pytest

from bench import check, gen, harness, reference

CFG = dict(n=1500, d=24, latent=4, noise=0.01, corpus_seed=3)


def numpy_topk(q, base, k, metric):
    if metric == "l2":
        d = ((q[:, None, :].astype(np.float64) - base[None]) ** 2).sum(-1)
    elif metric == "cos":
        qn = q / np.linalg.norm(q, axis=1, keepdims=True)
        bn = base / np.linalg.norm(base, axis=1, keepdims=True)
        d = 1.0 - qn.astype(np.float64) @ bn.T
    else:
        d = -(q.astype(np.float64) @ base.T)
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return ids, np.take_along_axis(d, ids, 1)


@pytest.mark.parametrize("metric", ["l2", "cos", "ip"])
def test_reference_matches_numpy(metric, monkeypatch):
    monkeypatch.setattr(reference, "CORPUS_BLOCK", 256)  # several blocks
    rng = np.random.default_rng(0)
    base = rng.normal(size=(1000, 16)).astype(np.float32)
    q = rng.normal(size=(9, 16)).astype(np.float32)
    ids, dists = reference.exact_topk(q, base, 10, metric)
    want_i, want_d = numpy_topk(q, base, 10, metric)
    np.testing.assert_array_equal(ids, want_i)
    np.testing.assert_allclose(dists, want_d, rtol=1e-5, atol=1e-5)
    pair, scale = reference.pair_distances(q, base, ids, metric)
    np.testing.assert_allclose(pair, want_d, rtol=1e-5, atol=1e-5)
    assert (scale > 0).all()


def test_generators_repeat_per_seed():
    a = np.asarray(gen.fresh_points(CFG, 50, 2**31 + 17, 4))
    b = np.asarray(gen.fresh_points(CFG, 50, 2**31 + 17, 4))
    c = np.asarray(gen.fresh_points(CFG, 50, 2**31 + 18, 4))
    np.testing.assert_array_equal(a, b)
    assert not np.allclose(a, c)
    np.testing.assert_array_equal(np.asarray(gen.corpus(CFG)),
                                  np.asarray(gen.corpus(dict(CFG))))
    np.testing.assert_array_equal(gen.arrivals(50, 4, 9), gen.arrivals(50, 4, 9))
    np.testing.assert_array_equal(gen.request_sizes([1, 2, 4], 30, 9),
                                  gen.request_sizes([1, 2, 4], 30, 9))


def test_seeds_reorder_the_same_work():
    a, b = gen.arrivals(80, 20, 1), gen.arrivals(80, 20, 2)
    assert len(a) == len(b) == 1600 and a[-1] == pytest.approx(20.0)
    np.testing.assert_allclose(np.sort(np.diff(a, prepend=0)),
                               np.sort(np.diff(b, prepend=0)))
    assert not np.allclose(a, b)
    s1, s2 = gen.request_sizes([1, 2, 4], 30, 1), gen.request_sizes([1, 2, 4], 30, 2)
    np.testing.assert_array_equal(np.sort(s1), np.sort(s2))


def test_queries_lie_on_the_corpus_manifold():
    """Fresh points share the corpus's lift: they sit as close to the
    corpus as the corpus's own points do, far closer than another lift's."""
    base = np.asarray(gen.corpus(CFG))
    q = np.asarray(gen.fresh_points(CFG, 20, 5, 4))
    other = np.asarray(gen.fresh_points(dict(CFG, corpus_seed=4), 20, 5, 4))
    _, dq = reference.exact_topk(q, base, 1, "l2")
    _, do = reference.exact_topk(other, base, 1, "l2")
    assert np.median(dq) * 10 < np.median(do)


def test_cache_key_follows_corpus_and_build_fields():
    cfg = json.load(open(os.path.join(harness.BENCH, "configs",
                                      "sift1m.json")))
    key = harness.cache_key(cfg)
    assert harness.cache_key(dict(cfg)) == key
    for field in harness.INDEX_KEYS:
        changed = dict(cfg)
        changed[field] = (changed[field] + 1 if isinstance(changed[field], int)
                          else str(changed[field]) + "x")
        assert harness.cache_key(changed) != key, field
    assert harness.cache_key(dict(cfg, ef=cfg["ef"] * 2)) == key


def test_cell_metrics_follow_benchmark_json():
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    names = [c["name"] for c in bench["workloads"]]
    for cell in names:
        e2e, layer = harness.cell_metrics(bench, cell)
        e2e_names = {m["name"] for m in e2e}
        assert {"setup_s", "recall_at_10"} <= e2e_names
        assert len(e2e_names) >= 3 and layer
        assert all(m["moves"] in e2e_names for m in layer)
        for m in layer:
            assert os.path.exists(os.path.join(harness.BENCH, "metrics",
                                               m["name"] + ".py"))


def test_unknown_device_kind_is_an_error():
    assert harness.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError):
        harness.device_peaks("cpu")


def test_malformed_rows():
    ids = np.array([[0, 1, 2], [3, 3, 4], [5, -1, 6], [7, 8, 9], [1, 2, 3]])
    d = np.array([[0, 1, 2], [0, 1, 2], [0, 1, 2], [0, 2, 1],
                  [0, 1, np.inf]], np.float32)
    np.testing.assert_array_equal(check.malformed_rows(ids, d, 9),
                                  [False, True, True, True, True])
