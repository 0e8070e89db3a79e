"""Tiered base store (DESIGN.md §9, §15): placement parity across
device/host/disk, bytes_touched accounting, bf16 residual storage, and the
streaming prefetch pipeline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bruteforce, diversify
from repro.core.base_store import (BaseStore, check_dtype, check_placement,
                                   rerank_gathered)
from repro.core.beam_search import INVALID, beam_traverse
from repro.core.engine import Searcher, SearchSpec

PQ = dict(scorer="pq", pq_m=8, pq_k=64)


@pytest.fixture(scope="module")
def world():
    key = jax.random.PRNGKey(17)
    base = jax.random.uniform(key, (1500, 16))
    queries = jax.random.uniform(jax.random.fold_in(key, 1), (24, 16))
    g = bruteforce.exact_knn_graph(base, 16)
    gd = diversify.build_gd_graph(base, g)
    gt = bruteforce.ground_truth(queries, base, 1)
    return base, queries, gd, gt


def test_placement_validation(world):
    base, *_ = world
    with pytest.raises(ValueError, match="base_placement"):
        check_placement("tape")
    with pytest.raises(ValueError, match="store_dtype"):
        check_dtype("f16")
    host = BaseStore(base, "host")
    with pytest.raises(ValueError, match="device-resident"):
        host.device_view()
    with pytest.raises(ValueError, match="placement"):
        BaseStore.wrap(host, "device")
    assert BaseStore.wrap(host, "host") is host


def test_gather_parity_and_accounting(world):
    """Host and device stores return identical rows; only the host store
    bills host traffic, at 4d bytes per VALID id."""
    base, *_ = world
    dev = BaseStore(base, "device")
    host = BaseStore(base, "host")
    ids = jnp.asarray([[0, 3, INVALID, 7], [9, INVALID, INVALID, 2]],
                      jnp.int32)
    r_dev, b_dev = dev.gather(ids)
    r_host, b_host = host.gather(ids)
    np.testing.assert_array_equal(np.asarray(r_dev), np.asarray(r_host))
    np.testing.assert_array_equal(np.asarray(b_dev), [0, 0])
    np.testing.assert_array_equal(np.asarray(b_host),
                                  [3 * host.row_bytes, 2 * host.row_bytes])
    assert host.gathered_rows == 5
    assert host.gathered_bytes == 5 * host.row_bytes
    assert dev.gathered_bytes == 0


def test_host_search_matches_device_exactly(world):
    """The acceptance bar: same survivors -> same rerank. ids, dists AND the
    comps bill are bit-identical across placements, and so is bytes_touched
    — device and host bill the same scored + rerank f32 rows, only their
    residency differs. On a chip the device rerank runs the native kernel (expanded-norm
    l2) and the tiers the reference formula, so there ids agree up to
    exact ties and distances to ~1e-6 relative; bit-identity holds on the
    reference dispatch (``base_store.rerank_gathered``)."""
    base, queries, gd, _ = world
    s = Searcher.from_graph(base, gd, key=jax.random.PRNGKey(2))
    spec = SearchSpec(ef=32, k=4, entry="projection", **PQ)
    dev = s.search(queries, spec)
    host = s.search(queries, spec._replace(base_placement="host"))
    np.testing.assert_array_equal(np.asarray(dev.ids), np.asarray(host.ids))
    np.testing.assert_array_equal(np.asarray(dev.dists),
                                  np.asarray(host.dists))
    np.testing.assert_array_equal(np.asarray(dev.n_comps),
                                  np.asarray(host.n_comps))
    np.testing.assert_array_equal(np.asarray(dev.bytes_touched),
                                  np.asarray(host.bytes_touched))
    # every row bills the pq-scored codes plus all ef rerank survivors at
    # 4d bytes each (rerank=0 -> whole list), so bytes sit strictly above
    # the rerank floor; the legacy host_bytes alias still reads
    assert int(host.host_bytes.min()) > 32 * 16 * 4


def test_disk_search_matches_host_and_device(world):
    """§15 acceptance: disk placement returns BIT-identical ids/dists/
    n_comps to host and device (same survivors, same f32 rerank rows read
    from mmap'd shards), and bills a positive page-granular byte count.
    On a chip the device rerank runs the native kernel (expanded-norm
    l2) and the tiers the reference formula, so there ids agree up to
    exact ties and distances to ~1e-6 relative; bit-identity holds on the
    reference dispatch (``base_store.rerank_gathered``)."""
    base, queries, gd, _ = world
    s = Searcher.from_graph(base, gd, key=jax.random.PRNGKey(2))
    spec = SearchSpec(ef=32, k=4, entry="projection", **PQ)
    dev = s.search(queries, spec)
    host = s.search(queries, spec._replace(base_placement="host"))
    disk = s.search(queries, spec._replace(base_placement="disk"))
    for a, b in ((dev, disk), (host, disk)):
        np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
        np.testing.assert_array_equal(np.asarray(a.dists),
                                      np.asarray(b.dists))
        np.testing.assert_array_equal(np.asarray(a.n_comps),
                                      np.asarray(b.n_comps))
    # page-granular billing: bytes_touched = scored codes (same traversal
    # as host, so same scored share) + whole 4 KiB pages for the rerank
    scored = np.asarray(host.bytes_touched) - 32 * 16 * 4
    pages = np.asarray(disk.bytes_touched) - scored
    assert (pages >= 4096).all()
    assert (pages % 4096 == 0).all()
    store = s.base_store("disk")
    assert store.gathered_rows > 0 and store.gathered_bytes > 0


def test_disk_store_spill_and_shards(world):
    """Spilled disk stores shard the base, mmap the shards back, gather
    across shard boundaries correctly, and free the spill dir on close."""
    import os

    base, *_ = world
    store = BaseStore(base, "disk", shard_rows=600)  # 1500 -> 3 shards
    assert len(store.shards) == 3
    ids = jnp.asarray([[0, 599, 600, 1499], [1200, INVALID, 42, 601]],
                      jnp.int32)
    rows, nbytes = store.gather(ids)
    ref = np.asarray(base)[np.asarray([[0, 599, 600, 1499],
                                       [1200, 0, 42, 601]])]
    ref[1, 1] = 0.0
    got = np.array(rows)
    got[1, 1] = 0.0
    np.testing.assert_allclose(got, ref, rtol=1e-6)
    assert (np.asarray(nbytes) > 0).all()
    spill = store.spill_dir
    assert spill is not None and os.path.isdir(spill)
    store.close()
    assert not os.path.exists(spill)


def test_bf16_store_halves_row_bytes(world):
    """store_dtype='bf16' keeps half the rerank bandwidth (row_bytes = 2d)
    and still recovers the true neighbors after the f32-dequant rerank."""
    base, queries, gd, gt = world
    s = Searcher.from_graph(base, gd, key=jax.random.PRNGKey(2))
    f32 = BaseStore(base, "host")
    bf16 = BaseStore(base, "host", dtype="bf16")
    assert bf16.row_bytes * 2 == f32.row_bytes
    spec = SearchSpec(ef=32, k=1, entry="projection",
                      base_placement="host", store_dtype="bf16", **PQ)
    res = s.search(queries, spec)
    assert float((res.ids[:, 0] == gt[:, 0]).mean()) >= 0.9
    # the billed rerank traffic halves with the row bytes: bf16 minus f32
    # bytes_touched differ exactly by 2d per reranked row
    f32_res = s.search(queries, spec._replace(store_dtype="f32"))
    diff = np.asarray(f32_res.bytes_touched) - np.asarray(res.bytes_touched)
    np.testing.assert_array_equal(diff, np.full(queries.shape[0],
                                                32 * 16 * 2))


def test_host_requires_base_free_scorer(world):
    base, queries, gd, _ = world
    s = Searcher.from_graph(base, gd)
    with pytest.raises(ValueError, match="scorer"):
        s.search(queries, SearchSpec(ef=16, base_placement="host"))
    with pytest.raises(ValueError, match="scorer"):
        s.search(queries, SearchSpec(ef=16, base_placement="disk"))
    with pytest.raises(ValueError, match="device"):
        s.search_with_trace(
            queries, SearchSpec(ef=16, base_placement="host", **PQ)
        )


def test_beam_traverse_rejects_base_bound_scorer(world):
    base, queries, gd, _ = world
    ent = jnp.zeros((queries.shape[0], 1), jnp.int32)
    with pytest.raises(ValueError, match="base-free"):
        beam_traverse(queries, gd.neighbors, ent, ef=8, scorer="exact")


def test_host_stream_pipeline_matches_monolithic(world):
    """The §9 prefetch pipeline (tile i's host rows in flight while tile i+1
    builds LUTs and traverses) is a throughput choice, not a semantic one —
    including the per-query host-traffic bill."""
    base, queries, gd, _ = world
    s = Searcher.from_graph(base, gd, key=jax.random.PRNGKey(2))
    spec = SearchSpec(ef=32, k=2, entry="projection", base_placement="host",
                      **PQ)
    mono = s.search(queries, spec)
    # tile_q=10 forces ragged last-tile padding (24 = 2*10 + 4)
    stream = s.search_stream(queries, spec, tile_q=10)
    np.testing.assert_array_equal(np.asarray(mono.ids),
                                  np.asarray(stream.ids))
    np.testing.assert_array_equal(np.asarray(mono.dists),
                                  np.asarray(stream.dists))
    np.testing.assert_array_equal(np.asarray(mono.n_comps),
                                  np.asarray(stream.n_comps))
    np.testing.assert_array_equal(np.asarray(mono.host_bytes),
                                  np.asarray(stream.host_bytes))


def test_rerank_budget_bounds_host_traffic(world):
    """spec.rerank caps the survivor slice, and with it the rerank share of
    bytes_touched — the knob that trades recall headroom for tier
    bandwidth. Both runs share the traversal (same seeds, same scorer), so
    the bytes delta is purely the (ef - rerank) rows the lean run skipped."""
    base, queries, gd, gt = world
    s = Searcher.from_graph(base, gd, key=jax.random.PRNGKey(2))
    full = s.search(queries, SearchSpec(ef=48, k=1, entry="projection",
                                        base_placement="host", **PQ))
    lean = s.search(queries, SearchSpec(ef=48, k=1, entry="projection",
                                        base_placement="host", rerank=8,
                                        **PQ))
    diff = np.asarray(full.bytes_touched) - np.asarray(lean.bytes_touched)
    np.testing.assert_array_equal(
        diff, np.full(queries.shape[0], (48 - 8) * 16 * 4))
    assert float((lean.ids[:, 0] == gt[:, 0]).mean()) >= 0.9
    # the searcher-level store totals accumulated both reranks' row traffic
    st = s.base_store("host")
    assert st.gathered_bytes == (48 + 8) * queries.shape[0] * 16 * 4


def test_rerank_gathered_matches_bruteforce(world):
    """The host rerank helper reproduces exact distances (ref formula) and
    sends INVALID survivors to the bottom."""
    base, queries, _, _ = world
    cand = jnp.asarray(
        np.r_[np.arange(7), [INVALID]][None].repeat(queries.shape[0], 0),
        jnp.int32,
    )
    store = BaseStore(base, "host")
    rows, _ = store.gather(cand)
    dd, ii = rerank_gathered(queries, cand, rows, k=3, metric="l2")
    ref = np.asarray(bruteforce.ground_truth(queries, base[:7], 3))
    np.testing.assert_array_equal(np.asarray(ii), ref)
    assert np.isfinite(np.asarray(dd)).all()
