"""Continuous-batching server (launch/server.py, DESIGN.md §11).

The load-bearing invariant: a request padded up to its bucket and searched
under the ``q_valid`` mask returns BIT-identical ids/dists/n_comps for its
real rows vs direct ``Searcher.search`` on those rows alone — across every
entry strategy, scorer, and base placement. Everything the server does
(bucketing, admission, overlap) rests on that; the rest of the file locks
the serving mechanics around it (bucket pick, shedding, timestamps, stats).
Served and direct searches run the same kernels, so the parity holds on a
chip as well.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bruteforce, diversify
from repro.core.engine import ENTRY_STRATEGIES, Searcher, SearchSpec
from repro.core.topk import INVALID
from repro.launch.server import AnnServer, Request, ServeConfig

Q_REAL = 11     # deliberately not a bucket size
BUCKET = 16


@pytest.fixture(scope="module")
def world():
    key = jax.random.PRNGKey(9)
    base = jax.random.uniform(key, (1500, 16))
    queries = jax.random.uniform(jax.random.fold_in(key, 1), (32, 16))
    searcher = Searcher.build(base, key=key, with_hierarchy=True)
    gt = bruteforce.ground_truth(queries, base, 1)
    return searcher, np.asarray(queries, np.float32), np.asarray(gt)


def padded_search(searcher, rows, spec, key, bucket):
    """The server's exact padding recipe (server._search_padded): seed on
    the REAL rows with the request key, then pad queries with zeros,
    entries with INVALID, entry comps with 0, and mask via q_valid. The key
    rides into the search too — restart keys are per-ROW-index, so the
    bucket shape must not change which restart seeds the real rows draw."""
    qn, d = rows.shape
    dev = jnp.asarray(rows)
    ent, ecomps = searcher.seed(dev, spec, key)
    pad = bucket - qn
    dev = jnp.concatenate([dev, jnp.zeros((pad, d), dev.dtype)])
    ent = jnp.concatenate(
        [ent, jnp.full((pad, ent.shape[1]), INVALID, jnp.int32)]
    )
    ecomps = jnp.concatenate([ecomps, jnp.zeros((pad,), ecomps.dtype)])
    return searcher.search(dev, spec, key, entries=ent, entry_comps=ecomps,
                           q_valid=jnp.arange(bucket) < qn)


SCORER_PLACEMENTS = [("exact", "device"), ("pq", "device"), ("pq", "host")]


@pytest.mark.parametrize("entry", sorted(ENTRY_STRATEGIES))
@pytest.mark.parametrize("scorer,placement", SCORER_PLACEMENTS,
                         ids=[f"{s}-{p}" for s, p in SCORER_PLACEMENTS])
def test_padding_parity(world, entry, scorer, placement):
    searcher, queries, _ = world
    spec = SearchSpec(ef=32, k=4, entry=entry, scorer=scorer,
                      base_placement=placement)
    if scorer == "pq":
        searcher.pq_index(spec)
    key = jax.random.fold_in(searcher.key, 123)
    rows = queries[:Q_REAL]

    direct = searcher.search(jnp.asarray(rows), spec, key)
    padded = padded_search(searcher, rows, spec, key, BUCKET)

    np.testing.assert_array_equal(np.asarray(padded.ids)[:Q_REAL],
                                  np.asarray(direct.ids))
    np.testing.assert_array_equal(np.asarray(padded.dists)[:Q_REAL],
                                  np.asarray(direct.dists))
    np.testing.assert_array_equal(np.asarray(padded.n_comps)[:Q_REAL],
                                  np.asarray(direct.n_comps))
    # padding rows: zero comparisons, no answers
    np.testing.assert_array_equal(np.asarray(padded.n_comps)[Q_REAL:], 0)
    assert (np.asarray(padded.ids)[Q_REAL:] == INVALID).all()


@pytest.mark.parametrize("entry", ["hubs", "hierarchy"])
def test_padding_parity_adaptive_termination(world, entry):
    """The §12 extension of the parity contract: per-query early freeze
    (term="stable") and fresh-seed restarts must survive bucketing. Frozen
    rows reuse the pad-row masking; restart seeds are fold_in(key, row), a
    function of the row index — so the padded search bit-matches direct on
    the real rows and pad rows still do zero work."""
    searcher, queries, _ = world
    spec = SearchSpec(ef=32, k=4, entry=entry, term="stable", stable_steps=4,
                      restarts=1)
    key = jax.random.fold_in(searcher.key, 321)
    rows = queries[:Q_REAL]

    direct = searcher.search(jnp.asarray(rows), spec, key)
    padded = padded_search(searcher, rows, spec, key, BUCKET)

    np.testing.assert_array_equal(np.asarray(padded.ids)[:Q_REAL],
                                  np.asarray(direct.ids))
    np.testing.assert_array_equal(np.asarray(padded.dists)[:Q_REAL],
                                  np.asarray(direct.dists))
    np.testing.assert_array_equal(np.asarray(padded.n_comps)[:Q_REAL],
                                  np.asarray(direct.n_comps))
    np.testing.assert_array_equal(np.asarray(padded.n_comps)[Q_REAL:], 0)
    assert (np.asarray(padded.ids)[Q_REAL:] == INVALID).all()


def test_server_adaptive_closed_loop_bit_matches_direct(world):
    """End-to-end through AnnServer with term="stable" + restarts: every
    completed request equals its direct-search twin (the CI serving smoke's
    adaptive leg, in miniature)."""
    searcher, queries, _ = world
    spec = SearchSpec(ef=32, k=2, entry="random", term="stable",
                      stable_steps=4, restarts=1)
    server = AnnServer(searcher, spec,
                       ServeConfig(buckets=(1, 2, 4, 8), max_live_batches=2,
                                   max_queue_depth=8))
    server.warmup()
    rng = np.random.default_rng(7)
    reqs = []
    for i in range(12):
        sz = int(rng.choice((1, 3, 5, 8)))
        start = int(rng.integers(0, queries.shape[0] - sz + 1))
        reqs.append((queries[start:start + sz],
                     jax.random.fold_in(searcher.key, 900 + i)))
    for rows, key in reqs:
        server.submit_wait(rows, key)
    server.drain()
    assert len(server.completed) == len(reqs) and not server.shed
    for req in sorted(server.completed, key=lambda r: r.rid):
        rows, key = reqs[req.rid]
        direct = searcher.search(jnp.asarray(rows), spec, key)
        np.testing.assert_array_equal(req.ids, np.asarray(direct.ids))
        np.testing.assert_array_equal(req.n_comps,
                                      np.asarray(direct.n_comps))


def test_all_true_mask_is_identity(world):
    searcher, queries, _ = world
    spec = SearchSpec(ef=32, k=4, entry="projection")
    q = jnp.asarray(queries[:8])
    ent, ecomps = searcher.seed(q, spec)
    a = searcher.search(q, spec, entries=ent, entry_comps=ecomps)
    b = searcher.search(q, spec, entries=ent, entry_comps=ecomps,
                        q_valid=jnp.ones(8, bool))
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.dists), np.asarray(b.dists))
    np.testing.assert_array_equal(np.asarray(a.n_comps),
                                  np.asarray(b.n_comps))


def test_server_closed_loop_bit_matches_direct(world):
    searcher, queries, _ = world
    spec = SearchSpec(ef=32, k=4, entry="random")
    server = AnnServer(searcher, spec,
                       ServeConfig(buckets=(1, 2, 4, 8), max_live_batches=2,
                                   max_queue_depth=8))
    server.warmup()
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(24):
        sz = int(rng.choice((1, 2, 3, 4, 5, 7, 8)))
        start = int(rng.integers(0, queries.shape[0] - sz + 1))
        reqs.append((queries[start:start + sz],
                     jax.random.fold_in(searcher.key, 500 + i)))
    for rows, key in reqs:
        server.submit_wait(rows, key)
    server.drain()

    assert len(server.completed) == len(reqs)
    assert not server.shed
    for req in sorted(server.completed, key=lambda r: r.rid):
        rows, key = reqs[req.rid]
        direct = searcher.search(jnp.asarray(rows), spec, key)
        np.testing.assert_array_equal(req.ids, np.asarray(direct.ids))
        np.testing.assert_array_equal(req.dists, np.asarray(direct.dists))
        np.testing.assert_array_equal(req.n_comps,
                                      np.asarray(direct.n_comps))


def test_pick_bucket():
    searcher_free = ServeConfig(buckets=(1, 2, 4, 8))
    srv = AnnServer.__new__(AnnServer)   # bucket logic needs no engine
    srv.config = searcher_free
    assert srv.pick_bucket(1) == 1
    assert srv.pick_bucket(3) == 4
    assert srv.pick_bucket(8) == 8
    with pytest.raises(ValueError, match="exceeds the largest bucket"):
        srv.pick_bucket(9)
    with pytest.raises(ValueError, match=">= 1 query row"):
        srv.pick_bucket(0)


def test_config_validation(world):
    searcher, _, _ = world
    spec = SearchSpec(ef=16, k=1, entry="random")
    with pytest.raises(ValueError, match="sorted unique positive"):
        AnnServer(searcher, spec, ServeConfig(buckets=(4, 2)))
    with pytest.raises(ValueError, match="sorted unique positive"):
        AnnServer(searcher, spec, ServeConfig(buckets=()))
    with pytest.raises(ValueError, match="max_live_batches"):
        AnnServer(searcher, spec, ServeConfig(max_live_batches=0))


def test_queue_depth_shedding(world):
    searcher, queries, _ = world
    spec = SearchSpec(ef=16, k=1, entry="random")
    server = AnnServer(searcher, spec,
                       ServeConfig(buckets=(1, 2), max_live_batches=1,
                                   max_queue_depth=2))
    server.warmup()
    # a backlogged listener enqueues without advancing the pipeline: the
    # queue holds 2, everything past that is shed (recorded, not dispatched)
    for i in range(6):
        server.submit(queries[i:i + 1], advance=False)
    assert len(server.queue) == 2
    assert len(server.shed) == 4
    assert all(r.shed and r.ids is None for r in server.shed)
    server.drain()
    assert len(server.completed) == 2
    st = server.stats()
    assert st["completed"] == 2 and st["shed"] == 4


def test_timestamps_and_stats(world):
    searcher, queries, _ = world
    spec = SearchSpec(ef=16, k=1, entry="random")
    server = AnnServer(searcher, spec,
                       ServeConfig(buckets=(1, 2, 4), max_live_batches=2,
                                   max_queue_depth=8))
    server.warmup()
    for i in range(10):
        server.submit_wait(queries[i:i + 1 + (i % 3)])
    server.drain()
    for req in server.completed:
        assert (req.t_enqueue <= req.t_admit <= req.t_dispatch
                <= req.t_complete)
        assert req.latency_s >= 0 and req.queue_wait_s >= 0
    st = server.stats()
    assert st["completed"] == 10
    assert st["p50_ms"] <= st["p90_ms"] <= st["p99_ms"]
    assert st["real_rows"] == sum(1 + (i % 3) for i in range(10))
    assert 0 < st["mean_fill"] <= 1
    assert sum(st["bucket_counts"].values()) == 10


def test_oversize_request_rejected(world):
    searcher, queries, _ = world
    spec = SearchSpec(ef=16, k=1, entry="random")
    server = AnnServer(searcher, spec, ServeConfig(buckets=(1, 2, 4)))
    with pytest.raises(ValueError, match="exceeds the largest bucket"):
        server.submit(queries[:5])


# -- hot swap (DESIGN.md §13) -------------------------------------------------


def _beam_cache_size():
    """Compiled-executable count of the beam core (None when this jax
    doesn't expose jit cache introspection)."""
    from repro.core import beam_search as bs

    fn = bs.beam_search
    if hasattr(fn, "_cache_size"):
        try:
            return int(fn._cache_size())
        except Exception:
            return None
    return None


def test_hot_swap_zero_drop_and_bit_identity(world):
    """One server across an index-version flip: requests already dispatched
    keep the OLD index, requests still queued at the flip get the NEW one,
    nothing is shed, and each side bit-matches direct search on the version
    that served it."""
    s0, queries, _ = world
    spec = SearchSpec(ef=32, k=4, entry="random")
    key2 = jax.random.PRNGKey(31)
    base2 = jax.random.uniform(key2, (900, 16))  # new n -> new core shapes
    s1 = Searcher.build(base2, key=key2)

    server = AnnServer(s0, spec,
                       ServeConfig(buckets=(1, 2, 4, 8), max_live_batches=2,
                                   max_queue_depth=16))
    server.warmup()
    assert server.version == 0 and server.swap_events == []

    rng = np.random.default_rng(13)
    def make(n, tag):
        reqs = []
        for i in range(n):
            sz = int(rng.choice((1, 3, 4, 8)))
            start = int(rng.integers(0, queries.shape[0] - sz + 1))
            reqs.append((queries[start:start + sz],
                         jax.random.fold_in(s0.key, tag + i)))
        return reqs

    reqs_a = make(6, 600)
    for rows, k in reqs_a:
        server.submit_wait(rows, k)
    server.drain()

    # enqueue WITHOUT admitting, then flip: the queued requests must come
    # back answered by the new version
    reqs_b = make(5, 700)
    for rows, k in reqs_b:
        server.submit(rows, k, advance=False)
    version = server.swap(s1, key=jax.random.fold_in(key2, 1))
    assert version == 1 and server.version == 1
    ev = server.swap_events[-1]
    assert ev["queued_at_flip"] == len(reqs_b) and ev["n"] == 900
    cache_at_flip = _beam_cache_size()

    server.drain()
    # no shape was traced after the flip — swap warmed the incoming index
    after = _beam_cache_size()
    assert cache_at_flip is None or after == cache_at_flip
    assert not server.shed
    assert len(server.completed) == len(reqs_a) + len(reqs_b)

    done = sorted(server.completed, key=lambda r: r.rid)
    for req, (rows, k) in zip(done[:len(reqs_a)], reqs_a):
        direct = s0.search(jnp.asarray(rows), spec, k)
        np.testing.assert_array_equal(req.ids, np.asarray(direct.ids))
        np.testing.assert_array_equal(req.dists, np.asarray(direct.dists))
    for req, (rows, k) in zip(done[len(reqs_a):], reqs_b):
        direct = s1.search(jnp.asarray(rows), spec, k)
        np.testing.assert_array_equal(req.ids, np.asarray(direct.ids))
        np.testing.assert_array_equal(req.dists, np.asarray(direct.dists))
        assert np.asarray(req.ids).max() < 900  # answered by the new index
    assert server.stats()["swaps"] == 1


def test_swap_warms_before_flip_not_after(world):
    """The p99-spike regression: every (qn, bucket) executable for the
    incoming index must exist BEFORE the flip, so the first post-flip
    request compiles nothing."""
    s0, queries, _ = world
    spec = SearchSpec(ef=16, k=2, entry="random")
    key2 = jax.random.PRNGKey(41)
    s1 = Searcher.build(jax.random.uniform(key2, (700, 16)), key=key2)
    server = AnnServer(s0, spec,
                       ServeConfig(buckets=(1, 2, 4), max_live_batches=2,
                                   max_queue_depth=8))
    server.warmup()
    server.swap(s1, key=jax.random.fold_in(key2, 2))
    before = _beam_cache_size()
    for i in range(1, 5):   # every qn the bucket set admits
        server.submit_wait(queries[:i], jax.random.fold_in(s1.key, 80 + i))
    server.drain()
    after = _beam_cache_size()
    assert before is None or after == before
    assert len(server.completed) == 4 and not server.shed
