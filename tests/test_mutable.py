"""Streaming index mutation (core/mutable.py, DESIGN.md §13).

The two load-bearing contracts:

* **Golden equivalence** — a ``construct='incremental'`` build with
  ``insert_ef=0`` (exact-scan maintenance) is BIT-IDENTICAL to the batch
  ``construct='exact'`` build: same neighbors, same edge distances. Inserts
  are not an approximation of a rebuild; at insert_ef=0 they ARE one.
* **Compaction = batch build** — after any insert/delete history, ``compact``
  with a given (spec, key) bit-matches ``build_index`` on the surviving rows
  with the same (spec, key), so a compacted index inherits every batch
  reproducibility guarantee.

Around those: tombstoned ids never appear in answers (any scorer, any base
placement), an all-zero tombstone bitmap is a bitwise no-op, and the
in-degree/hub statistics exclude dead vertices (the satellite regression).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bruteforce
from repro.core.build import BuildSpec, build_index
from repro.core.engine import Searcher, SearchSpec
from repro.core.graph_index import (hub_vertices, in_degree,
                                    in_degree_distribution)
from repro.core.mutable import MutableIndex, pack_tombstones
from repro.core.topk import INVALID

N, D = 500, 16


@pytest.fixture(scope="module")
def points():
    key = jax.random.PRNGKey(3)
    base = np.asarray(jax.random.uniform(key, (N, D)), np.float32)
    return base, key


@pytest.fixture(scope="module")
def built(points):
    base, key = points
    spec = BuildSpec(construct="nndescent", diversify="gd", graph_k=12,
                     nd_rounds=8, proxy_sample=0, lid_sample=0)
    return build_index(jnp.asarray(base), spec, key), spec


def _mutate(points, built):
    """One insert+delete history over a beam-maintained GD index."""
    base, key = points
    result, spec = built
    midx = MutableIndex.from_build(base, result, key=key, insert_ef=24,
                                   diversify="gd")
    extra = np.asarray(
        jax.random.uniform(jax.random.fold_in(key, 7), (40, D)), np.float32
    )
    new_ids = midx.insert_batch(extra)
    dead = np.random.default_rng(0).choice(N, size=N // 5, replace=False)
    midx.delete(dead)
    return midx, spec, dead, new_ids


@pytest.fixture(scope="module")
def mutated(points, built):
    """Shared by the read-only tests; the compact test (which remaps every
    id and clears the tombstones) builds its own instance via _mutate."""
    return _mutate(points, built)


def test_incremental_insert_ef0_bit_matches_exact_build(points):
    base, key = points
    kw = dict(diversify="none", graph_k=12, proxy_sample=0, lid_sample=0)
    inc = build_index(jnp.asarray(base),
                      BuildSpec(construct="incremental", insert_ef=0, **kw),
                      key)
    bat = build_index(jnp.asarray(base), BuildSpec(construct="exact", **kw),
                      key)
    np.testing.assert_array_equal(np.asarray(inc.graph.neighbors),
                                  np.asarray(bat.graph.neighbors))
    np.testing.assert_array_equal(np.asarray(inc.graph.dists),
                                  np.asarray(bat.graph.dists))
    # same graph -> same hub shortlist, and the report carries throughput
    np.testing.assert_array_equal(
        np.asarray(hub_vertices(inc.graph.neighbors)),
        np.asarray(hub_vertices(bat.graph.neighbors)))
    assert inc.report.inserts == N and inc.report.insert_rate > 0


def test_exact_maintenance_survives_capacity_growth():
    """Exact-mode inserts across two capacity doublings still reproduce the
    batch exact k-NN graph of the final point set, bit for bit."""
    key = jax.random.PRNGKey(5)
    pts = np.asarray(jax.random.uniform(key, (40, 8)), np.float32)
    midx = MutableIndex.empty(8, 6, capacity=16, insert_ef=0, key=key)
    midx.insert_batch(pts)
    assert midx.capacity == 64 and midx.n_live == 40
    g = bruteforce.exact_knn_graph(jnp.asarray(pts), 6)
    np.testing.assert_array_equal(midx.neighbors, np.asarray(g.neighbors))


def test_compact_bit_matches_fresh_build_of_survivors(points, built):
    midx, spec, dead, _new_ids = _mutate(points, built)
    survivors = midx.base[midx.alive].copy()
    n_alloc_pre = midx.n_alloc
    ckey = jax.random.fold_in(jax.random.PRNGKey(3), 9)
    cres = midx.compact(spec, ckey)
    fresh = build_index(jnp.asarray(survivors), spec, ckey)

    np.testing.assert_array_equal(np.asarray(cres.graph.neighbors),
                                  np.asarray(fresh.graph.neighbors))
    np.testing.assert_array_equal(midx.neighbors,
                                  np.asarray(fresh.graph.neighbors))
    np.testing.assert_array_equal(midx.base, np.asarray(survivors))
    assert midx.n_dead == 0 and midx.version == 1 and midx.staleness == 0.0
    # old->new id map: deleted ids map to INVALID, survivors stay in order
    id_map = midx.last_id_map
    assert (id_map[dead] == INVALID).all()
    live_old = np.nonzero(id_map != INVALID)[0]
    np.testing.assert_array_equal(id_map[live_old],
                                  np.arange(survivors.shape[0]))
    assert live_old.shape[0] == n_alloc_pre - dead.shape[0]
    # pre-compact churn is stamped on the compaction report
    assert cres.report.inserts == 40 and cres.report.staleness > 0


SCORER_PLACEMENTS = [("exact", "device"), ("pq", "device"), ("pq", "host"),
                     ("pq", "disk"), ("sq8", "disk")]


@pytest.mark.parametrize("scorer,placement", SCORER_PLACEMENTS,
                         ids=[f"{s}-{p}" for s, p in SCORER_PLACEMENTS])
def test_tombstoned_ids_never_served(points, mutated, scorer, placement):
    """No answer may name a deleted vertex — under the exact scorer AND the
    compressed-traversal scorers on every base placement (the tombstone
    bitmap rides the mask epilogue of gather_distance_masked,
    gather_adc_masked, and gather_sq8_masked alike)."""
    base, key = points
    midx, _spec, dead, _ = mutated
    queries = jnp.asarray(np.asarray(
        jax.random.uniform(jax.random.fold_in(key, 2), (16, D)), np.float32))
    sspec = SearchSpec(ef=48, k=8, entry="random", scorer=scorer,
                       base_placement=placement, pq_m=4, pq_k=16)
    searcher = midx.searcher()
    if scorer == "pq":
        searcher.pq_index(sspec)
    if placement != "device":
        searcher.base_store(placement)
    res = searcher.search(queries, sspec, jax.random.fold_in(key, 4))
    ids = np.asarray(res.ids)
    assert (ids != INVALID).any(), "searches returned nothing at all"
    assert not np.isin(ids[ids != INVALID], dead).any()
    # unallocated capacity slots are tombstoned too
    assert ids.max() < midx.n_alloc


def test_dead_seeds_are_redrawn_from_live_ids(points):
    """With 90% of the ids tombstoned, most random seed rows are all dead;
    seeding redraws dead seeds from the live ids, so every query still
    answers, and only with live ids."""
    base, key = points
    g = bruteforce.exact_knn_graph(jnp.asarray(base), 12)
    alive = np.arange(N) < N // 10
    s = Searcher(jnp.asarray(base), g.neighbors, key=key,
                 tombstones=jnp.asarray(pack_tombstones(~alive)))
    queries = jnp.asarray(np.asarray(
        jax.random.uniform(jax.random.fold_in(key, 5), (16, D)), np.float32))
    res = s.search(queries, SearchSpec(ef=16, k=4, entry="random"),
                   jax.random.fold_in(key, 6))
    ids = np.asarray(res.ids)
    assert (ids != INVALID).any(axis=1).all(), "a query answered nothing"
    assert alive[ids[ids != INVALID]].all()


def test_disk_tier_full_mutable_lifecycle(points, built):
    """§15 acceptance: the disk-backed rerank tier serves BIT-identical
    ids/dists/n_comps to device through a full insert -> delete -> compact
    lifecycle (the spilled shard set tracks every base the mutable index
    serves, and tombstones deny on disk exactly as on device).
    On a chip the device rerank runs the native kernel (expanded-norm
    l2) and the tiers the reference formula, so there ids agree up to
    exact ties and distances to ~1e-6 relative; bit-identity holds on the
    reference dispatch (``base_store.rerank_gathered``)."""
    base, key = points
    midx, spec, dead, _ = _mutate(points, built)
    queries = jnp.asarray(np.asarray(
        jax.random.uniform(jax.random.fold_in(key, 21), (12, D)), np.float32))
    sspec = SearchSpec(ef=32, k=4, entry="random", scorer="pq",
                       pq_m=4, pq_k=16)

    def disk_matches_device(s):
        skey = jax.random.fold_in(key, 22)
        dev = s.search(queries, sspec, skey)
        dsk = s.search(queries, sspec._replace(base_placement="disk"), skey)
        np.testing.assert_array_equal(np.asarray(dev.ids),
                                      np.asarray(dsk.ids))
        np.testing.assert_array_equal(np.asarray(dev.dists),
                                      np.asarray(dsk.dists))
        np.testing.assert_array_equal(np.asarray(dev.n_comps),
                                      np.asarray(dsk.n_comps))
        assert (np.asarray(dsk.bytes_touched) > 0).all()
        s.base_store("disk").close()  # free the spilled shard dir
        return np.asarray(dsk.ids)

    ids = disk_matches_device(midx.searcher())
    # tombstones deny on the disk tier too (ids are pre-compact numbering)
    assert not np.isin(ids[ids != INVALID], dead).any()
    midx.compact(spec, jax.random.fold_in(key, 23))
    disk_matches_device(midx.searcher())


def test_all_zero_tombstone_bitmap_is_identity(points):
    """tombstones=zeros(W) must be a bitwise no-op vs tombstones=None —
    the mutation path starts from exactly that state."""
    base, key = points
    g = bruteforce.exact_knn_graph(jnp.asarray(base), 12)
    plain = Searcher(jnp.asarray(base), g.neighbors, key=key)
    zeros = Searcher(jnp.asarray(base), g.neighbors, key=key,
                     tombstones=jnp.asarray(pack_tombstones(
                         np.zeros(N, bool))))
    queries = jnp.asarray(np.asarray(
        jax.random.uniform(jax.random.fold_in(key, 2), (8, D)), np.float32))
    sspec = SearchSpec(ef=32, k=4, entry="random")
    skey = jax.random.fold_in(key, 5)
    a, b = plain.search(queries, sspec, skey), zeros.search(queries, sspec,
                                                            skey)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.dists), np.asarray(b.dists))
    np.testing.assert_array_equal(np.asarray(a.n_comps),
                                  np.asarray(b.n_comps))


def test_delete_semantics(points):
    base, key = points
    g = bruteforce.exact_knn_graph(jnp.asarray(base), 8)
    midx = MutableIndex(base, np.asarray(g.neighbors), key=key)
    midx.delete([3, 5])
    assert midx.n_live == N - 2 and midx.n_dead == 2
    assert midx.staleness == pytest.approx(2 / (N - 2))
    with pytest.raises(KeyError):
        midx.delete(3)          # already dead
    with pytest.raises(KeyError):
        midx.delete(N + 100)    # never allocated
    alive = midx.alive
    assert not alive[3] and not alive[5] and alive.sum() == N - 2


def test_in_degree_and_hubs_mask_tombstones():
    """Satellite regression: edges INTO a dead vertex and edges FROM a dead
    row both vanish from the in-degree tally, and dead vertices never make
    the hub shortlist no matter how many stale edges still point at them."""
    nbrs = np.array([[1, 2], [2, 3], [1, -1], [1, 2]], np.int32)
    alive = np.array([True, True, True, False])
    # live-masked edges: 0->1, 0->2, 1->2, 2->1 (1->3 dead target; row 3
    # dead source). Unmasked the tally would read [0, 3, 3, 1].
    np.testing.assert_array_equal(in_degree(nbrs, alive), [0, 2, 2, 0])
    np.testing.assert_array_equal(in_degree(nbrs), [0, 3, 3, 1])
    hubs = np.asarray(hub_vertices(nbrs, 4, alive=alive))
    assert 3 not in hubs and set(hubs.tolist()) == {0, 1, 2}
    dist = in_degree_distribution(nbrs, alive)
    assert dist["max"] == 2  # live population only


def test_hub_shortlist_on_20pct_deleted_graph(points, mutated):
    base, key = points
    midx, _spec, dead, _ = mutated
    hubs = np.asarray(hub_vertices(midx.neighbors, 64, alive=midx.alive))
    assert hubs.shape[0] == 64
    assert not np.isin(hubs, dead).any()
    # the searcher the mutable index serves carries exactly this shortlist
    np.testing.assert_array_equal(np.asarray(midx.searcher().hubs), hubs)


def test_insert_is_searchable_immediately(points):
    base, key = points
    g = bruteforce.exact_knn_graph(jnp.asarray(base), 12)
    midx = MutableIndex(base, np.asarray(g.neighbors), key=key, insert_ef=32)
    x = np.asarray(
        jax.random.uniform(jax.random.fold_in(key, 11), (D,)), np.float32)
    new_id = midx.insert(x)
    assert new_id == N
    res = midx.search(jnp.asarray(x)[None, :],
                      SearchSpec(ef=48, k=1, entry="random"),
                      jax.random.fold_in(key, 12))
    assert int(res.ids[0, 0]) == new_id  # its own exact-duplicate query
    assert midx.stats()["pending_inserts"] == 1
    assert midx.insert_rate > 0
