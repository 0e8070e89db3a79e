"""Filtered & multi-tenant search (core/filters.py, DESIGN.md §14).

The load-bearing contracts:

* **Isolation** — under a tenant filter, no answer row ever names an id
  outside the tenant, for every scorer and base placement (the mask
  epilogue is the one place ids become distances, so denial there is
  total).
* **Quality** — filtered recall against a masked brute-force oracle
  tracks unfiltered recall at moderate selectivity (graph path) and is
  exact below ``filtered_brute_cutoff`` (exact-scan fallback).
* **Operands, not recompiles** — new filter values never trace a new
  beam executable, direct or served.
* **Parity** — a served request carrying a FilterSpec is bit-identical
  to direct filtered search on its rows (on a chip too: same kernels).
* **Composition** — tombstones ∨ filter; metadata rides artifacts (v3)
  and MutableIndex mutation untouched.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bruteforce, io
from repro.core.build import BuildSpec, build_index
from repro.core.engine import Searcher, SearchSpec, filtered_brute_cutoff
from repro.core.filters import (FilterSpec, bitmap_get, compile_filter,
                                pack_bitmap, unpack_bitmap)
from repro.core.mutable import MutableIndex
from repro.core.topk import INVALID
from repro.launch.server import AnnServer, ServeConfig

N, D, NQ, K, EF = 1500, 16, 24, 10, 64
N_TENANTS = 4

SCORER_PLACEMENTS = [("exact", "device"), ("pq", "device"), ("pq", "host")]


@pytest.fixture(scope="module", autouse=True)
def _drop_compile_caches():
    """This module compiles many beam-core variants (scorer x placement x
    batch shape, direct and served). On a long single-process run the
    accumulated XLA CPU executables can segfault a later, unrelated
    compile (observed in test_smoke_archs' GNN pjit) — drop the jit
    caches once the module is done so later modules compile fresh."""
    yield
    if hasattr(jax, "clear_caches"):
        jax.clear_caches()


@pytest.fixture(scope="module")
def world():
    key = jax.random.PRNGKey(11)
    base = jax.random.uniform(key, (N, D))
    queries = jax.random.uniform(jax.random.fold_in(key, 1), (NQ, D))
    rng = np.random.default_rng(0)
    metadata = {
        "tenant": rng.integers(0, N_TENANTS, size=N).astype(np.int32),
        "tag": rng.integers(0, 6, size=N).astype(np.int32),
        "timestamp": rng.random(N).astype(np.float32),
    }
    searcher = Searcher.build(base, key=key)
    searcher.metadata = metadata
    return searcher, np.asarray(base, np.float32), \
        np.asarray(queries, np.float32), metadata


def _spec(searcher, scorer="exact", placement="device", **kw):
    spec = SearchSpec(ef=EF, k=K, scorer=scorer, base_placement=placement,
                      **kw)
    if scorer == "pq":
        searcher.pq_index(spec)
    return spec


def _allowed_mask(metadata, f: FilterSpec) -> np.ndarray:
    allow = np.ones(len(metadata["tenant"]), bool)
    if f.tenant is not None:
        allow &= metadata["tenant"] == f.tenant
    if f.tags_any:
        allow &= np.isin(metadata["tag"], np.asarray(f.tags_any))
    if f.time_range is not None:
        lo, hi = f.time_range
        allow &= (metadata["timestamp"] >= lo) & (metadata["timestamp"] <= hi)
    if f.deny_ids:
        allow[np.asarray(f.deny_ids)] = False
    return allow


def _masked_oracle(queries, base, allow, k):
    """Brute-force top-k over the allowed rows, mapped back to global ids."""
    gt = bruteforce.ground_truth(jnp.asarray(queries[:, :]),
                                 jnp.asarray(base[allow]), k)
    return np.nonzero(allow)[0][np.asarray(gt)]


def _recall(ids, oracle):
    hits = sum(len(set(a[a >= 0].tolist()) & set(o.tolist()))
               for a, o in zip(np.asarray(ids), oracle))
    return hits / oracle.size


# -- bitmap + compile unit layer ---------------------------------------------


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(7)
    for n in (1, 31, 32, 33, 257, 1500):
        bits = rng.random(n) < 0.3
        words = pack_bitmap(bits)
        assert words.shape == ((n + 31) // 32,) and words.dtype == np.uint32
        np.testing.assert_array_equal(unpack_bitmap(words, n), bits)


def test_bitmap_get_invalid_reads_false():
    words = jnp.asarray(pack_bitmap(np.ones(64, bool)))
    got = bitmap_get(words, jnp.asarray([0, 5, INVALID, -7]))
    np.testing.assert_array_equal(np.asarray(got),
                                  [True, True, False, False])


def test_compile_filter_matches_numpy_predicate(world):
    _, _, _, metadata = world
    f = FilterSpec(tenant=2, time_range=(0.1, 0.8), deny_ids=(3, 5))
    cf = compile_filter(f, metadata, N)
    allow = _allowed_mask(metadata, f)
    assert cf.n_allowed == int(allow.sum())
    np.testing.assert_array_equal(unpack_bitmap(np.asarray(cf.deny), N),
                                  ~allow)
    ids = np.asarray(cf.allowed_ids)
    np.testing.assert_array_equal(ids[:cf.n_allowed], np.nonzero(allow)[0])
    assert (ids[cf.n_allowed:] == INVALID).all()
    # power-of-two padded fallback operand
    assert ids.shape[0] & (ids.shape[0] - 1) == 0


def test_compile_filter_composes_tombstones(world):
    _, _, _, metadata = world
    dead = np.zeros(N, bool)
    dead[:50] = True
    f = FilterSpec(tenant=1)
    cf = compile_filter(f, metadata, N, dead=pack_bitmap(dead))
    allow = _allowed_mask(metadata, f) & ~dead
    assert cf.n_allowed == int(allow.sum())
    np.testing.assert_array_equal(unpack_bitmap(np.asarray(cf.deny), N),
                                  ~allow)


def test_missing_column_is_loud(world):
    searcher, _, queries, _ = world
    meta, searcher.metadata = searcher.metadata, {"tenant":
                                                  searcher.metadata["tenant"]}
    searcher._filters.clear()
    try:
        with pytest.raises(ValueError, match="timestamp.*carries.*tenant"):
            searcher.search(jnp.asarray(queries[:4]),
                            _spec(searcher)._replace(
                                filter=FilterSpec(time_range=(0.0, 0.5))),
                            key=jax.random.PRNGKey(0))
    finally:
        searcher.metadata = meta
        searcher._filters.clear()


# -- recall vs the masked oracle ---------------------------------------------


@pytest.mark.parametrize("scorer,placement", SCORER_PLACEMENTS,
                         ids=[f"{s}-{p}" for s, p in SCORER_PLACEMENTS])
@pytest.mark.parametrize("sel", [0.9, 0.5, 0.01])
def test_filtered_recall_vs_masked_oracle(world, scorer, placement, sel):
    searcher, base, queries, metadata = world
    spec = _spec(searcher, scorer, placement)
    f = FilterSpec(time_range=(0.0, sel))
    key = jax.random.fold_in(searcher.key, 77)

    res = searcher.search(jnp.asarray(queries),
                          spec._replace(filter=f), key)
    allow = _allowed_mask(metadata, f)
    ids = np.asarray(res.ids)

    # isolation: every returned id satisfies the predicate
    assert allow[ids[ids >= 0]].all()

    oracle = _masked_oracle(queries, base, allow, K)
    filt = _recall(ids, oracle)
    if allow.sum() <= filtered_brute_cutoff(spec):
        # exact-scan fallback: recall 1 by construction, comps = n_allowed
        assert filt == 1.0
        np.testing.assert_array_equal(np.asarray(res.n_comps),
                                      int(allow.sum()))
    else:
        unf = _recall(np.asarray(searcher.search(
            jnp.asarray(queries), spec, key).ids),
            np.asarray(bruteforce.ground_truth(
                jnp.asarray(queries), jnp.asarray(base), K)))
        assert filt >= 0.92 * unf, (filt, unf)


def test_empty_filter_contract(world):
    """A filter matching nothing: all-INVALID answers, zero comparisons."""
    searcher, _, queries, _ = world
    spec = _spec(searcher)
    res = searcher.search(
        jnp.asarray(queries[:8]),
        spec._replace(filter=FilterSpec(time_range=(2.0, 3.0))),
        key=jax.random.PRNGKey(5))
    assert (np.asarray(res.ids) == INVALID).all()
    assert not np.isfinite(np.asarray(res.dists)).any()
    np.testing.assert_array_equal(np.asarray(res.n_comps), 0)


@pytest.mark.parametrize("scorer,placement", SCORER_PLACEMENTS,
                         ids=[f"{s}-{p}" for s, p in SCORER_PLACEMENTS])
def test_tenant_isolation(world, scorer, placement):
    searcher, _, queries, metadata = world
    spec = _spec(searcher, scorer, placement)
    for t in range(N_TENANTS):
        res = searcher.search(
            jnp.asarray(queries),
            spec._replace(filter=FilterSpec(tenant=t)),
            key=jax.random.fold_in(searcher.key, t))
        ids = np.asarray(res.ids)
        valid = ids >= 0
        assert valid.any()
        assert (metadata["tenant"][ids[valid]] == t).all(), \
            f"tenant {t} leak under {scorer}/{placement}"


def test_deny_ids_suppress_known_answers(world):
    searcher, base, queries, _ = world
    spec = _spec(searcher)
    key = jax.random.fold_in(searcher.key, 13)
    top = np.asarray(searcher.search(jnp.asarray(queries), spec, key).ids)
    deny = tuple(sorted({int(i) for i in top[:, 0] if i >= 0}))
    res = searcher.search(jnp.asarray(queries),
                          spec._replace(filter=FilterSpec(deny_ids=deny)),
                          key)
    assert not np.isin(np.asarray(res.ids), np.asarray(deny)).any()


def test_search_stream_filtered(world):
    """Tiled filtered search: same isolation, comparable quality (per-tile
    seed keys differ from the full batch, so parity is statistical)."""
    searcher, base, queries, metadata = world
    spec = _spec(searcher)
    f = FilterSpec(time_range=(0.0, 0.9))
    key = jax.random.fold_in(searcher.key, 31)
    tiled = searcher.search_stream(jnp.asarray(queries),
                                   spec._replace(filter=f), key, tile_q=8)
    allow = _allowed_mask(metadata, f)
    ids = np.asarray(tiled.ids)
    assert allow[ids[ids >= 0]].all()
    oracle = _masked_oracle(queries, base, allow, K)
    assert _recall(ids, oracle) >= 0.85


# -- operands, not recompiles ------------------------------------------------


def _beam_cache_size():
    from repro.core import beam_search as bs

    fn = bs.beam_search
    if hasattr(fn, "_cache_size"):
        try:
            return int(fn._cache_size())
        except Exception:
            return None
    return None


def test_filter_values_do_not_recompile(world):
    searcher, _, queries, _ = world
    spec = _spec(searcher)
    key = jax.random.PRNGKey(21)
    q = jnp.asarray(queries[:NQ])
    # first filtered search traces the deny-operand variant once
    searcher.search(q, spec._replace(filter=FilterSpec(tenant=0)), key)
    before = _beam_cache_size()
    for f in (FilterSpec(tenant=1), FilterSpec(tenant=2),
              FilterSpec(time_range=(0.0, 0.9)),
              FilterSpec(tags_any=(1, 3)), FilterSpec(deny_ids=(7, 8))):
        searcher.search(q, spec._replace(filter=f), key)
    after = _beam_cache_size()
    assert before is None or after == before
    # and the compiled-filter cache holds one entry per distinct FilterSpec
    assert len(searcher._filters) >= 6


def test_filter_cache_lru_eviction_and_recompile(world):
    """The compiled-filter cache is a bounded LRU (filter_cache_size,
    default 64): distinct filter values evict oldest-first past the bound,
    touching a resident entry refreshes it, and an evicted filter costs
    exactly one recompile when it returns — operand memory stays O(bound),
    not O(distinct filters ever seen)."""
    searcher, _, queries, _ = world
    spec = _spec(searcher)
    key = jax.random.PRNGKey(33)
    q = jnp.asarray(queries[:4])
    old_cap = searcher.filter_cache_size
    searcher._filters.clear()
    searcher.filter_cache_size = 4
    try:
        filters = [FilterSpec(tenant=t % N_TENANTS, tags_any=(t,))
                   for t in range(6)]
        base_compiles = searcher.filter_compiles
        for f in filters:
            searcher.search(q, spec._replace(filter=f), key)
        assert searcher.filter_compiles == base_compiles + 6
        assert list(searcher._filters) == filters[2:]  # oldest two evicted
        # resident hit: no recompile, entry moves to most-recent
        searcher.search(q, spec._replace(filter=filters[2]), key)
        assert searcher.filter_compiles == base_compiles + 6
        assert next(iter(reversed(searcher._filters))) == filters[2]
        # an evicted filter recompiles once and displaces the current LRU
        searcher.search(q, spec._replace(filter=filters[0]), key)
        assert searcher.filter_compiles == base_compiles + 7
        assert len(searcher._filters) == 4
        assert filters[3] not in searcher._filters
        assert filters[0] in searcher._filters
    finally:
        searcher.filter_cache_size = old_cap
        searcher._filters.clear()


# -- served parity -----------------------------------------------------------


def test_served_mixed_filters_bit_match_direct(world):
    searcher, _, queries, _ = world
    spec = _spec(searcher)
    server = AnnServer(searcher, spec, ServeConfig(buckets=(4, 8)))
    server.warmup(jax.random.PRNGKey(2))
    cache_after_warmup = _beam_cache_size()

    filters = [None, FilterSpec(tenant=1),
               FilterSpec(time_range=(0.0, 0.5)),
               FilterSpec(time_range=(0.0, 0.01)),   # exact-scan fallback
               FilterSpec(deny_ids=(1, 2, 3)), FilterSpec(tenant=3)]
    reqs = []
    for i, f in enumerate(filters):
        rows = queries[i: i + 3 + (i % 4)]
        reqs.append(server.submit_wait(
            rows, jax.random.fold_in(searcher.key, 900 + i), filter=f))
    server.drain()
    # mixed filter values over warmed buckets trace nothing new
    assert cache_after_warmup is None or \
        _beam_cache_size() == cache_after_warmup

    for f, req in zip(filters, reqs):
        s = spec if f is None else spec._replace(filter=f)
        direct = searcher.search(jnp.asarray(req.queries), s, req.key)
        np.testing.assert_array_equal(req.ids, np.asarray(direct.ids))
        np.testing.assert_array_equal(req.dists, np.asarray(direct.dists))
        np.testing.assert_array_equal(req.n_comps,
                                      np.asarray(direct.n_comps))


# -- persistence + mutation --------------------------------------------------


def test_artifact_v3_metadata_roundtrip(world, tmp_path):
    searcher, _, queries, metadata = world
    art = io.IndexArtifact.from_searcher(searcher)
    path = io.save_index(str(tmp_path / "idx"), art)
    loaded = io.load_index(path)
    assert sorted(loaded.metadata) == sorted(metadata)
    for name in metadata:
        np.testing.assert_array_equal(loaded.metadata[name], metadata[name])

    s2 = loaded.to_searcher()
    f = FilterSpec(tenant=2, time_range=(0.0, 0.7))
    key = jax.random.PRNGKey(4)
    spec = _spec(searcher)
    a = searcher.search(jnp.asarray(queries), spec._replace(filter=f), key)
    b = s2.search(jnp.asarray(queries), spec._replace(filter=f), key)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))


def test_artifact_without_metadata_still_loads(world, tmp_path):
    searcher, _, _, _ = world
    import dataclasses

    art = dataclasses.replace(io.IndexArtifact.from_searcher(searcher),
                              metadata=None)
    path = io.save_index(str(tmp_path / "bare"), art)
    loaded = io.load_index(path)
    assert loaded.metadata is None
    assert loaded.to_searcher().metadata is None


def test_mutable_metadata_lifecycle(tmp_path):
    key = jax.random.PRNGKey(6)
    n0, d = 300, 16
    base = np.asarray(jax.random.uniform(key, (n0, d)), np.float32)
    rng = np.random.default_rng(3)
    meta = {"tenant": rng.integers(0, 3, size=n0).astype(np.int32)}
    bspec = BuildSpec(construct="nndescent", diversify="gd", graph_k=12,
                      nd_rounds=8, proxy_sample=0, lid_sample=0)
    result = build_index(jnp.asarray(base), bspec, key)
    midx = MutableIndex.from_build(base, result, key=key, insert_ef=24,
                                   metadata=meta)

    # inserts carry per-row metadata; unknown columns are rejected loudly
    extra = np.asarray(jax.random.uniform(jax.random.fold_in(key, 1),
                                          (20, d)), np.float32)
    new_ids = midx.insert_batch(
        extra, metadata={"tenant": np.full(20, 1, np.int32)})
    with pytest.raises(ValueError, match="declare"):
        midx.insert(extra[0], metadata={"color": 3})

    # tombstones and filters compose: delete some tenant-1 rows, then a
    # tenant-1 filter must exclude BOTH other tenants and the deleted rows
    dead = [int(i) for i in new_ids[:5]]
    midx.delete(dead)
    s = midx.searcher()
    spec = SearchSpec(ef=48, k=8)
    res = s.search(jnp.asarray(base[:16]),
                   spec._replace(filter=FilterSpec(tenant=1)),
                   key=jax.random.fold_in(key, 9))
    ids = np.asarray(res.ids)
    valid = ids >= 0
    tenant_col = midx.metadata["tenant"]
    assert (tenant_col[ids[valid]] == 1).all()
    assert not np.isin(ids[valid], np.asarray(dead)).any()

    # compaction drops dead rows but keeps surviving metadata aligned
    id_map_len = midx.n_alloc
    midx.compact(bspec, key=jax.random.fold_in(key, 2))
    surv = midx.metadata["tenant"]
    assert surv.shape[0] == id_map_len - len(dead)
    assert (surv >= 0).all()

    # checkpoint -> artifact -> from_artifact round-trips the columns
    path, _ = midx.checkpoint(str(tmp_path / "ck"), bspec,
                              key=jax.random.fold_in(key, 8))
    midx2 = MutableIndex.from_artifact(io.load_index(path))
    np.testing.assert_array_equal(midx2.metadata["tenant"],
                                  midx.metadata["tenant"])
