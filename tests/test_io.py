"""IndexArtifact round-trip parity + on-disk format guards.

The acceptance contract: a saved-then-loaded artifact yields BIT-IDENTICAL
search results (ids/dists/n_comps) to the in-memory build — for flat,
GD/DPG-diversified, hierarchical, and PQ-compressed indexes, under both
base placements. Both sides run the same kernels, so this holds on a chip
as well."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import io as rio
from repro.core.build import BuildSpec, build_index
from repro.core.engine import Searcher, SearchSpec

PQ_BUILD = dict(compress="pq", pq_m=8, pq_k=32)
PQ_SEARCH = dict(scorer="pq", pq_m=8, pq_k=32)

# case -> (BuildSpec kwargs, SearchSpec kwargs). Every diversify scheme, the
# hierarchy, and the compressed scorer under both placements are covered.
CASES = {
    "flat": (dict(construct="exact", diversify="none", graph_k=12),
             dict(ef=32, k=2, entry="projection")),
    "gd": (dict(construct="nndescent", diversify="gd", graph_k=12,
                nd_rounds=6),
           dict(ef=32, k=2, entry="random")),
    "dpg": (dict(construct="exact", diversify="dpg", graph_k=12),
            dict(ef=32, k=2, entry="lsh")),
    "hier": (dict(construct="hnsw", diversify="none", graph_k=12),
             dict(ef=32, k=2, entry="hierarchy")),
    "pq_device": (dict(construct="exact", diversify="gd", graph_k=12,
                       **PQ_BUILD),
                  dict(ef=32, k=2, entry="projection", **PQ_SEARCH)),
    "pq_host": (dict(construct="exact", diversify="gd", graph_k=12,
                     **PQ_BUILD),
                dict(ef=32, k=2, entry="projection", base_placement="host",
                     **PQ_SEARCH)),
    # hub seeding + adaptive termination + restarts: the persisted hub
    # shortlist AND the persisted PRNG key must both travel for this one to
    # replay bit-identically (restart seeds derive from the searcher key)
    "hubs": (dict(construct="nndescent", diversify="gd", graph_k=12,
                  nd_rounds=6),
             dict(ef=32, k=2, entry="hubs", term="stable", stable_steps=6,
                  restarts=1)),
}


@pytest.fixture(scope="module")
def world():
    key = jax.random.PRNGKey(17)
    base = jax.random.uniform(key, (800, 16))
    queries = jax.random.uniform(jax.random.fold_in(key, 1), (16, 16))
    return base, queries


@pytest.fixture(scope="module")
def built(world):
    """One build per distinct BuildSpec (pq_device/pq_host share one)."""
    base, _ = world
    cache = {}
    out = {}
    for name, (bkw, _skw) in CASES.items():
        spec = BuildSpec(**bkw)
        if spec not in cache:
            cache[spec] = build_index(base, spec, key=jax.random.PRNGKey(23))
        out[name] = cache[spec]
    return out


@pytest.mark.parametrize("case", sorted(CASES))
def test_roundtrip_search_is_bit_identical(world, built, case, tmp_path):
    base, queries = world
    _bkw, skw = CASES[case]
    res = built[case]
    spec = SearchSpec(**skw)
    mem = Searcher.from_build(base, res, key=jax.random.PRNGKey(23))
    want = mem.search(queries, spec)

    path = rio.save_index(
        os.path.join(tmp_path, case),
        rio.IndexArtifact.from_build(base, res, metric="l2",
                                     key=jax.random.PRNGKey(23)),
    )
    got = rio.load_index(path).to_searcher().search(queries, spec)
    np.testing.assert_array_equal(np.asarray(want.ids), np.asarray(got.ids))
    np.testing.assert_array_equal(np.asarray(want.dists),
                                  np.asarray(got.dists))
    np.testing.assert_array_equal(np.asarray(want.n_comps),
                                  np.asarray(got.n_comps))
    if spec.base_placement == "host":
        np.testing.assert_array_equal(np.asarray(want.host_bytes),
                                      np.asarray(got.host_bytes))
        assert int(got.host_bytes.min()) > 0


def test_loaded_pq_never_retrains(world, built, tmp_path):
    """The serve fix: a loaded artifact carries its code table — pq_index
    returns it as-is instead of re-running k-means at startup."""
    base, _ = world
    res = built["pq_device"]
    path = rio.save_index(
        os.path.join(tmp_path, "pq"),
        rio.IndexArtifact.from_build(base, res, metric="l2"),
    )
    s = rio.load_index(path).to_searcher()
    idx = s.pq_index(SearchSpec(**PQ_SEARCH))
    assert idx is s._pq_attached  # served, not trained
    np.testing.assert_array_equal(np.asarray(idx.codes),
                                  np.asarray(res.pq.codes))


def test_manifest_contents(world, built, tmp_path):
    base, _ = world
    res = built["hier"]
    path = rio.save_index(
        os.path.join(tmp_path, "m"),
        rio.IndexArtifact.from_build(base, res, metric="l2",
                                     key=jax.random.PRNGKey(23)),
    )
    m = json.loads(str(np.load(path)["manifest"][()]))
    assert m["format"] == rio.FORMAT_MAGIC
    assert m["version"] == rio.ARTIFACT_VERSION
    assert (m["n"], m["d"]) == (800, 16)
    assert m["num_layers"] == res.hierarchy.num_layers
    assert m["provenance"]["build_report"]["spec"]["construct"] == "hnsw"
    assert m["provenance"]["build_report"]["degree"]["max"] >= 1


def test_from_searcher_persists_lazily_trained_pq(world, tmp_path):
    base, queries = world
    s = Searcher.build(base, key=jax.random.PRNGKey(2), graph_k=10)
    s.pq_index(SearchSpec(**PQ_SEARCH))  # lazy train
    path = rio.save_index(os.path.join(tmp_path, "lazy"),
                          rio.IndexArtifact.from_searcher(s))
    art = rio.load_index(path)
    assert art.pq is not None and (art.pq.M, art.pq.K) == (8, 32)
    spec = SearchSpec(ef=24, k=1, entry="projection", **PQ_SEARCH)
    want = s.search(queries, spec)
    got = art.to_searcher().search(queries, spec)
    np.testing.assert_array_equal(np.asarray(want.ids), np.asarray(got.ids))


def test_legacy_flat_npz_still_loads(world, tmp_path):
    """Pre-manifest serve format {base, neighbors, metric} loads as v0."""
    base, queries = world
    res = build_index(base, BuildSpec(construct="exact", graph_k=10))
    path = os.path.join(tmp_path, "legacy.npz")
    np.savez(path, base=np.asarray(base),
             neighbors=np.asarray(res.graph.neighbors), metric="l2")
    art = rio.load_index(path)
    assert art.version == 0 and art.provenance.get("legacy")
    r = art.to_searcher().search(queries,
                                 SearchSpec(ef=24, k=1, entry="projection"))
    assert r.ids.shape == (queries.shape[0], 1)


def test_hubs_persist_bit_identically(world, built, tmp_path):
    """v2 artifacts carry the hub shortlist; the loaded array is bit-equal
    to the build-time one AND to a fresh recompute from the adjacency (the
    derivation is deterministic — stable argsort, ties to lowest id)."""
    from repro.core.graph_index import hub_vertices

    base, _ = world
    res = built["hubs"]
    path = rio.save_index(
        os.path.join(tmp_path, "h"),
        rio.IndexArtifact.from_build(base, res, metric="l2",
                                     key=jax.random.PRNGKey(23)),
    )
    art = rio.load_index(path)
    assert art.hubs is not None
    np.testing.assert_array_equal(np.asarray(art.hubs), np.asarray(res.hubs))
    np.testing.assert_array_equal(
        np.asarray(art.hubs),
        np.asarray(hub_vertices(res.graph.neighbors, art.hubs.shape[0])),
    )
    assert art.degree_stats["in"]["hub_mass"] > 0
    m = json.loads(str(np.load(path)["manifest"][()]))
    assert m["n_hubs"] == art.hubs.shape[0]
    assert m["degree_stats"]["out"]["mean"] > 0


def test_v1_artifact_recomputes_hubs(world, built, tmp_path):
    """Artifacts written before hub persistence (schema v1) load with the
    shortlist recomputed from the adjacency — bit-identical to what a fresh
    build would persist — and hub-seeded search replays unchanged."""
    from repro.core.graph_index import hub_vertices

    base, queries = world
    res = built["hubs"]
    art = rio.IndexArtifact.from_build(base, res, metric="l2",
                                       key=jax.random.PRNGKey(23))
    path = rio.save_index(os.path.join(tmp_path, "v1"), art)
    # rewrite as a v1 artifact: drop the hubs array + v2 manifest keys
    blob = dict(np.load(path, allow_pickle=False))
    m = json.loads(str(blob.pop("manifest")[()]))
    m["version"] = 1
    del m["n_hubs"], m["degree_stats"]
    del blob["hubs"]
    np.savez(path, manifest=np.array(json.dumps(m)), **blob)

    old = rio.load_index(path)
    assert old.version == 1
    np.testing.assert_array_equal(
        np.asarray(old.hubs),
        np.asarray(hub_vertices(old.neighbors, old.hubs.shape[0])),
    )
    np.testing.assert_array_equal(np.asarray(old.hubs), np.asarray(res.hubs))
    spec = SearchSpec(**CASES["hubs"][1])
    want = Searcher.from_build(base, res,
                               key=jax.random.PRNGKey(23)).search(queries,
                                                                  spec)
    got = old.to_searcher().search(queries, spec)
    np.testing.assert_array_equal(np.asarray(want.ids), np.asarray(got.ids))
    np.testing.assert_array_equal(np.asarray(want.n_comps),
                                  np.asarray(got.n_comps))


def test_hubs_array_shape_mismatch_rejected(world, built, tmp_path):
    """A v2 artifact whose hubs array disagrees with manifest n_hubs is
    corrupt and must fail loudly."""
    base, _ = world
    path = rio.save_index(
        os.path.join(tmp_path, "trunc"),
        rio.IndexArtifact.from_build(base, built["hubs"], metric="l2"),
    )
    blob = dict(np.load(path, allow_pickle=False))
    blob["hubs"] = blob["hubs"][:3]
    np.savez(path, **blob)
    with pytest.raises(ValueError, match="n_hubs|corrupt"):
        rio.load_index(path)


def test_newer_schema_version_rejected(tmp_path):
    path = os.path.join(tmp_path, "future.npz")
    manifest = {"format": rio.FORMAT_MAGIC,
                "version": rio.ARTIFACT_VERSION + 1,
                "metric": "l2", "n": 1, "d": 1, "degree": 1}
    np.savez(path, manifest=np.array(json.dumps(manifest)),
             base=np.zeros((1, 1), np.float32),
             neighbors=np.zeros((1, 1), np.int32))
    with pytest.raises(ValueError, match="newer"):
        rio.load_index(path)


def test_wrong_magic_rejected(tmp_path):
    path = os.path.join(tmp_path, "alien.npz")
    np.savez(path, manifest=np.array(json.dumps({"format": "other"})))
    with pytest.raises(ValueError, match="format"):
        rio.load_index(path)


def test_shape_mismatch_rejected(world, tmp_path):
    """A manifest whose shapes disagree with the arrays (truncated write,
    hand-edited file) must fail loudly, not search garbage."""
    path = os.path.join(tmp_path, "corrupt.npz")
    manifest = {"format": rio.FORMAT_MAGIC, "version": rio.ARTIFACT_VERSION,
                "metric": "l2", "n": 999, "d": 16, "degree": 4}
    np.savez(path, manifest=np.array(json.dumps(manifest)),
             base=np.zeros((10, 16), np.float32),
             neighbors=np.zeros((10, 4), np.int32))
    with pytest.raises(ValueError, match="corrupt|disagree"):
        rio.load_index(path)


def test_suffixless_path_normalized(world, built, tmp_path):
    base, _ = world
    p = rio.save_index(os.path.join(tmp_path, "noext"),
                       rio.IndexArtifact.from_build(base, built["flat"],
                                                    metric="l2"))
    assert p.endswith(".npz") and os.path.exists(p)
    assert rio.load_index(os.path.join(tmp_path, "noext")).n == 800


# -- crash safety (DESIGN.md §13: the hot-swap producer side) -----------------


def test_truncated_artifact_raises_named_error(world, built, tmp_path):
    """A partial write (every truncation point, not just 'half') must raise
    CorruptArtifactError — never a raw zipfile/zlib/KeyError traceback — so
    a hot-swapping server can catch one exception type and keep serving its
    current version."""
    base, _ = world
    path = rio.save_index(os.path.join(tmp_path, "whole.npz"),
                          rio.IndexArtifact.from_build(base, built["flat"],
                                                       metric="l2"))
    blob = open(path, "rb").read()
    for frac in (0.05, 0.5, 0.98):
        cut = os.path.join(tmp_path, f"cut{int(frac * 100)}.npz")
        with open(cut, "wb") as f:
            f.write(blob[: int(len(blob) * frac)])
        with pytest.raises(rio.CorruptArtifactError):
            rio.load_index(cut)


def test_save_is_atomic_kill_mid_write_keeps_old_artifact(world, built,
                                                          tmp_path,
                                                          monkeypatch):
    """Simulated kill mid-save: np.savez dies after emitting partial bytes.
    The final path must still hold the OLD complete artifact (save writes a
    temp file and os.replace's it only on success), and the dead temp file
    must not be left behind."""
    base, _ = world
    path = os.path.join(tmp_path, "index.npz")
    rio.save_index(path, rio.IndexArtifact.from_build(base, built["flat"],
                                                      metric="l2"))
    before = open(path, "rb").read()

    real_savez = np.savez

    def dying_savez(f, **arrays):
        real_savez(f, **arrays)           # bytes hit the temp file...
        raise KeyboardInterrupt("kill -9 mid-save")   # ...then the "crash"

    monkeypatch.setattr(np, "savez", dying_savez)
    with pytest.raises(KeyboardInterrupt):
        rio.save_index(path, rio.IndexArtifact.from_build(base, built["gd"],
                                                          metric="l2"))
    monkeypatch.undo()

    assert open(path, "rb").read() == before      # old artifact untouched
    assert [p for p in os.listdir(tmp_path)
            if p.endswith(".tmp")] == []          # no temp litter
    art = rio.load_index(path)                    # and it still loads whole
    assert art.n == base.shape[0]


def test_save_replaces_existing_artifact_atomically(world, built, tmp_path):
    """Happy-path overwrite goes through the same temp+rename: the new
    artifact lands complete and the temp name is gone."""
    base, _ = world
    path = os.path.join(tmp_path, "swap.npz")
    rio.save_index(path, rio.IndexArtifact.from_build(base, built["flat"],
                                                      metric="l2"))
    first = rio.load_index(path)
    rio.save_index(path, rio.IndexArtifact.from_build(base, built["gd"],
                                                      metric="l2"))
    second = rio.load_index(path)
    assert not np.array_equal(np.asarray(first.neighbors),
                              np.asarray(second.neighbors))
    assert [p for p in os.listdir(tmp_path) if p.endswith(".tmp")] == []


# -- v4: sharded base + disk tier substrate + OPQ rotation (DESIGN.md §15) ----


def test_sharded_artifact_roundtrip_bit_identical(world, built, tmp_path):
    """shard_rows moves the base into sibling .npy files the manifest names;
    the loaded artifact searches bit-identically to the unsharded build."""
    base, queries = world
    res = built["pq_device"]
    spec = SearchSpec(ef=32, k=2, entry="projection", **PQ_SEARCH)
    want = Searcher.from_build(base, res,
                               key=jax.random.PRNGKey(23)).search(queries,
                                                                  spec)
    path = rio.save_index(
        os.path.join(tmp_path, "sharded"),
        rio.IndexArtifact.from_build(base, res, metric="l2",
                                     key=jax.random.PRNGKey(23)),
        shard_rows=300,
    )
    names = rio.shard_file_names(path, 3)          # 800 rows -> 300/300/200
    assert all(os.path.exists(os.path.join(tmp_path, f)) for f in names)
    blob = np.load(path, allow_pickle=False)
    assert "base" not in blob.files                # base left the npz
    m = json.loads(str(blob["manifest"][()]))
    assert m["shards"] == {"files": names, "rows": [300, 300, 200],
                           "dtype": "f32"}
    got = rio.load_index(path).to_searcher().search(queries, spec)
    np.testing.assert_array_equal(np.asarray(want.ids), np.asarray(got.ids))
    np.testing.assert_array_equal(np.asarray(want.dists),
                                  np.asarray(got.dists))
    np.testing.assert_array_equal(np.asarray(want.n_comps),
                                  np.asarray(got.n_comps))


def test_open_base_shards_feeds_disk_store(world, built, tmp_path):
    """The serving path: open_base_shards mmaps the shard set and
    BaseStore.from_shards adopts it without copying — gathers across shard
    boundaries reproduce the original rows."""
    from repro.core.base_store import BaseStore

    base, _ = world
    path = rio.save_index(
        os.path.join(tmp_path, "mm"),
        rio.IndexArtifact.from_build(base, built["flat"], metric="l2"),
        shard_rows=300,
    )
    shards, dt = rio.open_base_shards(path)
    assert dt == "f32" and len(shards) == 3
    store = BaseStore.from_shards(shards, dt)
    assert (store.n, store.d) == (800, 16)
    ids = jnp.asarray([[0, 299, 300, 799]], jnp.int32)
    rows, nbytes = store.gather(ids)
    np.testing.assert_allclose(np.asarray(rows)[0],
                               np.asarray(base)[[0, 299, 300, 799]],
                               rtol=1e-6)
    assert int(np.asarray(nbytes)[0]) > 0
    # unsharded artifacts refuse the mmap path with a pointed message
    flat = rio.save_index(os.path.join(tmp_path, "nosh"),
                          rio.IndexArtifact.from_build(base, built["flat"],
                                                       metric="l2"))
    with pytest.raises(ValueError, match="not sharded"):
        rio.open_base_shards(flat)


def test_bf16_shards_halve_disk_bytes(world, built, tmp_path):
    """shard_dtype='bf16' stores half-width residuals: shard files shrink,
    from_shards serves 2d-byte rows, and load_index dequantizes to f32
    within bf16 rounding."""
    from repro.core.base_store import BaseStore

    base, _ = world
    art = rio.IndexArtifact.from_build(base, built["flat"], metric="l2")
    p32 = rio.save_index(os.path.join(tmp_path, "w32"), art, shard_rows=400)
    p16 = rio.save_index(os.path.join(tmp_path, "w16"), art, shard_rows=400,
                         shard_dtype="bf16")
    s32 = os.path.getsize(os.path.join(tmp_path,
                                       rio.shard_file_names(p32, 2)[0]))
    s16 = os.path.getsize(os.path.join(tmp_path,
                                       rio.shard_file_names(p16, 2)[0]))
    assert s16 < s32  # 400*16 rows at 2 vs 4 bytes/elem (+ equal headers)
    shards, dt = rio.open_base_shards(p16)
    assert dt == "bf16"
    assert BaseStore.from_shards(shards, dt).row_bytes == 16 * 2
    loaded = rio.load_index(p16)
    assert loaded.base.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(loaded.base), np.asarray(base),
                               atol=0.5 / 128)  # bf16: 8-bit mantissa


def test_corrupt_shards_raise_named_error(world, built, tmp_path):
    """A damaged shard set — truncated, missing, or shape-mismatched shard —
    fails as CorruptArtifactError on BOTH the in-memory and mmap loaders,
    never a raw numpy traceback."""
    base, _ = world
    art = rio.IndexArtifact.from_build(base, built["flat"], metric="l2")

    def fresh(tag):
        d = tmp_path / tag
        d.mkdir()
        p = rio.save_index(os.path.join(d, "a"), art, shard_rows=300)
        return p, [os.path.join(d, f) for f in rio.shard_file_names(p, 3)]

    path, shards = fresh("trunc")
    blob = open(shards[1], "rb").read()
    with open(shards[1], "wb") as f:
        f.write(blob[: len(blob) // 2])
    for loader in (rio.load_index, rio.open_base_shards):
        with pytest.raises(rio.CorruptArtifactError):
            loader(path)

    path, shards = fresh("missing")
    os.unlink(shards[2])
    for loader in (rio.load_index, rio.open_base_shards):
        with pytest.raises(rio.CorruptArtifactError, match="missing"):
            loader(path)

    path, shards = fresh("shape")
    np.save(shards[0], np.zeros((5, 16), np.float32))
    for loader in (rio.load_index, rio.open_base_shards):
        with pytest.raises(rio.CorruptArtifactError, match="disagrees"):
            loader(path)


def test_v3_artifact_loads_unchanged(world, built, tmp_path):
    """Pre-shard artifacts (schema v3: base inside the npz, pq manifest
    without a rotation flag) load bit-identically under the v4 loader."""
    base, queries = world
    res = built["pq_device"]
    spec = SearchSpec(ef=32, k=2, entry="projection", **PQ_SEARCH)
    want = Searcher.from_build(base, res,
                               key=jax.random.PRNGKey(23)).search(queries,
                                                                  spec)
    path = rio.save_index(
        os.path.join(tmp_path, "v3"),
        rio.IndexArtifact.from_build(base, res, metric="l2",
                                     key=jax.random.PRNGKey(23)),
    )
    blob = dict(np.load(path, allow_pickle=False))
    m = json.loads(str(blob.pop("manifest")[()]))
    m["version"] = 3
    del m["shards"]            # v3 manifests predate the shard table...
    del m["pq"]["rotation"]    # ...and the OPQ rotation flag
    np.savez(path, manifest=np.array(json.dumps(m)), **blob)
    art = rio.load_index(path)
    assert art.version == 3 and art.pq.rotation is None
    got = art.to_searcher().search(queries, spec)
    np.testing.assert_array_equal(np.asarray(want.ids), np.asarray(got.ids))
    np.testing.assert_array_equal(np.asarray(want.dists),
                                  np.asarray(got.dists))
    np.testing.assert_array_equal(np.asarray(want.n_comps),
                                  np.asarray(got.n_comps))


def test_opq_rotation_roundtrip(world, built, tmp_path):
    """An attached OPQ table persists its learned rotation: the array
    round-trips bit-exactly and rotated-query search replays unchanged."""
    from repro.baselines.pq import build_opq, derive_opq_key

    base, queries = world
    key = jax.random.PRNGKey(23)
    opq = build_opq(base, M=8, K=32, key=derive_opq_key(key))
    s = Searcher.from_graph(base, built["gd"].graph, key=key, pq=opq)
    spec = SearchSpec(ef=32, k=2, entry="projection", **PQ_SEARCH)
    want = s.search(queries, spec)
    path = rio.save_index(os.path.join(tmp_path, "opq"),
                          rio.IndexArtifact.from_searcher(s))
    m = json.loads(str(np.load(path)["manifest"][()]))
    assert m["pq"] == {"m": 8, "k": 32, "rotation": True}
    art = rio.load_index(path)
    np.testing.assert_array_equal(np.asarray(art.pq.rotation),
                                  np.asarray(opq.rotation))
    got = art.to_searcher().search(queries, spec)
    np.testing.assert_array_equal(np.asarray(want.ids), np.asarray(got.ids))
    np.testing.assert_array_equal(np.asarray(want.dists),
                                  np.asarray(got.dists))
    np.testing.assert_array_equal(np.asarray(want.n_comps),
                                  np.asarray(got.n_comps))
