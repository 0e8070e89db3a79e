"""Engine smoke benchmark — the perf trajectory's first data point.

Runs every registered entry strategy through the one SearchEngine on a small
synthetic world and emits ``BENCH_engine.json`` with recall@1, comparisons
per query, and wall time per strategy, plus the beam-core batched-search
timing (the number the hot-loop perf work is tracked against, and the one
``benchmarks/check_regression.py`` guards in CI) and a streaming (Q, n, d)
sweep comparing one monolithic batch against tiled ``search_stream`` serving.

    PYTHONPATH=src python -m benchmarks.smoke --out BENCH_engine.json
"""
from __future__ import annotations

import argparse
import json
import sys

sys.path.insert(0, "src")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import bruteforce, diversify  # noqa: E402
from repro.core.engine import ENTRY_STRATEGIES, Searcher, SearchSpec  # noqa: E402
from repro.launch.runtime import enable_compile_cache  # noqa: E402

try:
    from .bench_util import timeit  # noqa: E402
    from .loadgen import serving_sweep  # noqa: E402
except ImportError:  # run as a plain script: python benchmarks/smoke.py
    from bench_util import timeit  # noqa: E402
    from loadgen import serving_sweep  # noqa: E402

# Streaming sweep worlds: (Q, n, d). Kept small — graphs here are exact k-NN
# (no NN-Descent) so the sweep adds seconds, not minutes, to CI.
STREAM_SWEEP = [(256, 3000, 16), (384, 2000, 32), (512, 1500, 24)]

# Quantization-ladder sweep dimensions: (d, pq_M). Memory ratio of the
# scored base is 4d/M for pq and 4x for sq8 — the curse-of-dimensionality
# axis the compressed traversal attacks. The world is ANISOTROPIC (decaying
# per-dim variance under a random rotation): uniform cubes give OPQ's
# learned rotation nothing to recover, real embedding spectra do.
PQ_SWEEP = [(16, 8), (64, 8), (128, 16)]

# Three-tier base sweep (DESIGN.md §9, §15): fixed (d, M), n grows past what
# a device-resident float base would allow; every n runs the SAME pq spec
# with the base on device, host and disk. PR CI runs the main-world n only;
# the nightly job passes --host-tier-ns 6000,60000,200000.
HOST_TIER_D = 16
HOST_TIER_M = 8

# Build sweep (DESIGN.md §10): construct × diversify over the MAIN world
# (same base/queries/gt so the search-recall column is comparable across
# rows) — the paper's Fig. 4/5 axis: flat construct + diversification vs
# the hierarchy, now swept through one BuildSpec per row. All rows search
# with the same random-entry spec: the contrast is pure build choice.
BUILD_SWEEP = [
    ("exact", "none"),
    ("exact", "gd"),
    ("exact", "dpg"),
    ("nndescent", "gd"),
    ("nndescent", "dpg"),
    ("hnsw", "none"),
]
BUILD_SWEEP_K = 16       # raw degree out of the construct stage
BUILD_SWEEP_ROUNDS = 8   # NN-Descent budget (the smoke world converges well
                         # before; the report's `rounds` column shows it)

# Streaming-mutation sweep (DESIGN.md §13): one insert/delete/compact
# lifecycle per insert_ef on a dedicated world. Columns: sustained insert
# throughput, staleness at compaction time, recall served off the tombstoned
# graph vs after the merge-compaction, and the bit-gate that compaction
# equals a fresh batch build of the survivors. Small world: every insert is
# a real beam dispatch, so n_inserts bounds the sweep's wall.
MUTATION_WORLD = (3000, 16)        # (n, d)
MUTATION_INSERT_EFS = (32, 64)
MUTATION_INSERTS = 200
MUTATION_DELETE_FRAC = 0.15

# Entry x termination sweep (DESIGN.md §12): the hot-path waste attack.
# recall@k over a top-k objective (k=1 freezes too eagerly to be a fair
# stability signal); stable rows run at a RAISED ef ceiling — the point of
# per-query termination is that easy queries freeze early while hard ones
# keep the larger budget, so the ceiling stops pricing every query.
ENTRY_TERM_K = 10
ENTRY_TERM_ENTRIES = ("random", "hierarchy", "hubs")
ENTRY_TERM_EF_FACTOR = 2       # stable ceiling = factor * fixed ef
ENTRY_TERM_STABLE_STEPS = 20   # patience: steps without top-k improvement
ENTRY_TERM_RESTARTS = 2        # the one restarts>0 row (GNNS-style reseed)

# Filtered-search sweep (DESIGN.md §14): selectivity x scorer x placement
# over the MAIN world with a uniform timestamp column. The 0.01 row drops
# below filtered_brute_cutoff and exercises the exact-scan fallback (recall
# 1.0 by construction, comps == n_allowed); the graph rows are gated by
# check_regression on recall ratio vs the same spec unfiltered, and every
# row on zero isolation violations against the numpy predicate.
FILTERED_SELECTIVITIES = (0.9, 0.5, 0.01)
FILTERED_COMBOS = (("exact", "device"), ("pq", "device"), ("pq", "host"))
FILTERED_K = 10
FILTERED_EF_FACTOR = 3         # filtered rows search at factor * ef: denied
                               # regions thin the traversable graph, so the
                               # beam needs headroom to route around them
                               # (2x leaves sel=0.5 at ~0.949 of unfiltered
                               # on the CI world — just under the 0.95 gate)


def _build_graph(base, key):
    """Exact k-NN graph below the brute-force knee, NN-Descent above it —
    the host-tier worlds are the only smoke worlds big enough to need it."""
    from repro.core import nndescent

    if base.shape[0] <= 8000:
        g = bruteforce.exact_knn_graph(base, 16)
    else:
        g = nndescent.build_knn_graph(
            base, nndescent.NNDescentConfig(k=16, rounds=6), key=key
        )
    return diversify.build_gd_graph(base, g)


def _host_tier_sweep(key, ns, q, ef, out, main_world=None) -> list[dict]:
    """device/host/disk base placement at growing n (same graph, same PQ,
    same seeds): recall must be bit-parity across all three tiers (identical
    survivors -> identical rerank), qps loss bounded by the gather tail, and
    ``*_bytes_per_query`` (§15) records what each tier actually touches —
    identical scored+rerank bytes for device/host (same f32 rows, different
    residency), unique 4 KiB pages for disk.

    ``main_world`` is the already-built (n, searcher, queries, gt) of the
    main report: a sweep point at that n reuses it (per-push CI runs the
    sweep at the main n only — rebuilding the world would double the
    dominant graph-build/PQ-train cost of every tier1 leg)."""
    rows = []
    for i, n in enumerate(ns):
        if main_world is not None and n == main_world[0] \
                and main_world[1].base.shape[1] == HOST_TIER_D:
            _, s, queries, gt = main_world
            neighbors = s.neighbors
        else:
            kw = jax.random.fold_in(key, 300 + i)
            base = jax.random.uniform(kw, (n, HOST_TIER_D))
            queries = jax.random.uniform(
                jax.random.fold_in(kw, 1), (q, HOST_TIER_D)
            )
            gd = _build_graph(base, jax.random.fold_in(kw, 2))
            s = Searcher.from_graph(base, gd, key=kw)
            neighbors = gd.neighbors
            gt = bruteforce.ground_truth(queries, base, 1)

        spec_ex = SearchSpec(ef=ef, k=1, entry="random")
        spec_dev = SearchSpec(ef=ef, k=1, entry="random", scorer="pq",
                              pq_m=HOST_TIER_M)
        spec_host = spec_dev._replace(base_placement="host")
        spec_disk = spec_dev._replace(base_placement="disk")
        # one seed draw shared by all four runs: the tier contrast must be
        # pure placement, and exact-vs-pq pure scorer
        ent, extra = s.seed(queries, spec_dev)
        s.pq_index(spec_dev)        # code table trained off the timer
        s.base_store("host")        # host mirror materialized off the timer
        disk_store = s.base_store("disk")   # shards spilled off the timer
        run = lambda sp: s.search(queries, sp, entries=ent, entry_comps=extra)
        _, res_ex = timeit(run, spec_ex, iters=1)
        wall_dev, res_dev = timeit(run, spec_dev, iters=2)
        wall_host, res_host = timeit(run, spec_host, iters=2)
        wall_disk, res_disk = timeit(run, spec_disk, iters=2)

        parity = float((res_dev.ids[:, 0] == res_host.ids[:, 0]).mean())
        parity_disk = float((res_dev.ids[:, 0] == res_disk.ids[:, 0]).mean())
        row = {
            "n": n, "d": HOST_TIER_D, "pq_m": HOST_TIER_M,
            "exact_recall_at_1": round(
                float((res_ex.ids[:, 0] == gt[:, 0]).mean()), 4),
            "device_recall_at_1": round(
                float((res_dev.ids[:, 0] == gt[:, 0]).mean()), 4),
            "host_recall_at_1": round(
                float((res_host.ids[:, 0] == gt[:, 0]).mean()), 4),
            "disk_recall_at_1": round(
                float((res_disk.ids[:, 0] == gt[:, 0]).mean()), 4),
            "host_device_parity": round(parity, 4),
            "disk_device_parity": round(parity_disk, 4),
            "device_wall_ms": round(wall_dev * 1e3, 2),
            "host_wall_ms": round(wall_host * 1e3, 2),
            "disk_wall_ms": round(wall_disk * 1e3, 2),
            "device_qps": round(q / wall_dev, 1),
            "host_qps": round(q / wall_host, 1),
            "disk_qps": round(q / wall_disk, 1),
            "qps_ratio": round(wall_dev / wall_host, 4),
            "disk_qps_ratio": round(wall_dev / wall_disk, 4),
            "exact_bytes_per_query": round(
                float(res_ex.bytes_touched.mean()), 1),
            "device_bytes_per_query": round(
                float(res_dev.bytes_touched.mean()), 1),
            "host_bytes_per_query": round(
                float(res_host.bytes_touched.mean()), 1),
            "disk_bytes_per_query": round(
                float(res_disk.bytes_touched.mean()), 1),
            "device_float_mb": round(n * HOST_TIER_D * 4 / 2**20, 2),
            "device_resident_mb": round(
                (n * HOST_TIER_M + neighbors.size * 4) / 2**20, 2),
        }
        rows.append(row)
        # drop the spilled shard tmpdir once the row is measured (the
        # nightly 200k world would otherwise hold its shards until exit)
        s._stores.pop(("disk", "f32"), None)
        disk_store.close()
        out(f"smoke/host_tier n={n}: recall exact={row['exact_recall_at_1']:.3f} "
            f"dev={row['device_recall_at_1']:.3f} "
            f"host={row['host_recall_at_1']:.3f} "
            f"disk={row['disk_recall_at_1']:.3f} "
            f"parity host={parity:.3f} disk={parity_disk:.3f}, "
            f"qps {row['device_qps']:.0f}->{row['host_qps']:.0f}->"
            f"{row['disk_qps']:.0f}, bytes/q "
            f"{row['device_bytes_per_query']:.0f}/"
            f"{row['host_bytes_per_query']:.0f}/"
            f"{row['disk_bytes_per_query']:.0f}, "
            f"device {row['device_float_mb']:.1f}->"
            f"{row['device_resident_mb']:.1f} MB")
    return rows


def _build_sweep(base, queries, gt, ef: int, key, out) -> list[dict]:
    """One BuildSpec per (construct, diversify) row, all over the main
    world: build wall (per stage), graph-recall proxy, realized degree,
    dropped reverse edges, memory, then search recall/comps at a fixed
    random-entry spec — the build-side perf trajectory check_regression
    guards (wall, proxy, recall)."""
    from repro.core.build import BuildSpec, GraphBuilder

    rows = []
    for construct, diversify in BUILD_SWEEP:
        spec = BuildSpec(construct=construct, diversify=diversify,
                         graph_k=BUILD_SWEEP_K, nd_rounds=BUILD_SWEEP_ROUNDS)
        res = GraphBuilder(spec).build(base, key=key)
        rep = res.report
        s = Searcher.from_build(base, res, key=key)
        sres = s.search(queries, SearchSpec(ef=ef, k=1, entry="random"))
        row = {
            "construct": construct,
            "diversify": diversify,
            "build_wall_ms": round(rep.wall_total_s * 1e3, 1),
            "construct_wall_ms": round(rep.wall_construct_s * 1e3, 1),
            "diversify_wall_ms": round(rep.wall_diversify_s * 1e3, 1),
            "rounds": rep.rounds,
            "graph_recall_proxy": rep.graph_recall_proxy,
            "degree_mean": rep.degree["mean"],
            "degree_max": rep.degree["max"],
            "dropped_reverse_edges": rep.dropped_reverse_edges,
            "lid": rep.lid,
            "hub_mass": rep.in_degree.get("hub_mass"),
            "memory_mb": round(rep.memory_bytes / 2**20, 2),
            "recall_at_1": round(
                float((sres.ids[:, 0] == gt[:, 0]).mean()), 4),
            "comps_per_query": round(float(sres.n_comps.mean()), 1),
        }
        rows.append(row)
        out(f"smoke/build {construct}·{diversify}: "
            f"wall={row['build_wall_ms']:.0f}ms "
            f"proxy={row['graph_recall_proxy']:.3f} "
            f"deg={row['degree_mean']:.1f}/{row['degree_max']} "
            f"dropped={row['dropped_reverse_edges']} "
            f"recall={row['recall_at_1']:.3f} "
            f"comps={row['comps_per_query']:.0f}")
    return rows


def _mutation_sweep(key, q: int, ef: int, out) -> list[dict]:
    """Streaming-mutation trajectory (DESIGN.md §13): per insert_ef, run
    build -> insert wave -> 15% tombstones -> merge-compaction on the
    MUTATION_WORLD, recording insert throughput, staleness, recall off the
    tombstoned graph (live ground truth) and post-compact recall, plus the
    compaction bit-gate. check_regression guards throughput/recall drift
    once a baseline carries the sweep; the bit-gate is baseline-free."""
    from repro.core.build import BuildSpec, build_index
    from repro.core.mutable import MutableIndex

    n, d = MUTATION_WORLD
    kw = jax.random.fold_in(key, 500)
    base = jax.random.uniform(kw, (n, d))
    queries = jax.random.uniform(jax.random.fold_in(kw, 1), (q, d))
    bspec = BuildSpec(construct="nndescent", diversify="gd", graph_k=16,
                      nd_rounds=BUILD_SWEEP_ROUNDS, proxy_sample=0,
                      lid_sample=0)
    result = build_index(base, bspec, kw)
    extra = np.asarray(jax.random.uniform(jax.random.fold_in(kw, 2),
                                          (MUTATION_INSERTS, d)), np.float32)
    dead = np.random.default_rng(0).choice(
        n, size=int(MUTATION_DELETE_FRAC * n), replace=False)

    rows = []
    for i, ief in enumerate(MUTATION_INSERT_EFS):
        midx = MutableIndex.from_build(np.asarray(base), result, key=kw,
                                       insert_ef=ief, diversify="gd")
        midx.insert_batch(extra)
        midx.delete(dead)
        staleness = midx.staleness
        spec = SearchSpec(ef=ef, k=1, entry="random")

        # recall over the tombstoned graph, against LIVE-set ground truth
        alive_ids = np.nonzero(midx.alive)[0]
        live_base = jax.numpy.asarray(midx.base[alive_ids])
        gt_live = alive_ids[np.asarray(
            bruteforce.ground_truth(queries, live_base, 1))[:, 0]]
        res = midx.search(queries, spec, jax.random.fold_in(kw, 30 + i))
        pre_recall = float((np.asarray(res.ids[:, 0]) == gt_live).mean())

        survivors = midx.base[midx.alive].copy()
        ckey = jax.random.fold_in(kw, 40 + i)
        cres = midx.compact(bspec, ckey)
        fresh = build_index(jax.numpy.asarray(survivors), bspec, ckey)
        compact_ok = bool(np.array_equal(
            np.asarray(cres.graph.neighbors),
            np.asarray(fresh.graph.neighbors)))
        gt_post = np.asarray(bruteforce.ground_truth(
            queries, jax.numpy.asarray(midx.base), 1))[:, 0]
        res2 = midx.search(queries, spec, jax.random.fold_in(kw, 50 + i))
        post_recall = float((np.asarray(res2.ids[:, 0]) == gt_post).mean())

        row = {
            "n": n, "d": d, "insert_ef": ief,
            "inserts": MUTATION_INSERTS,
            "deletes": int(dead.shape[0]),
            "insert_rate": round(midx.insert_rate, 1),
            "staleness": round(staleness, 4),
            "pre_compact_recall_at_1": round(pre_recall, 4),
            "post_compact_recall_at_1": round(post_recall, 4),
            "compact_wall_ms": round(cres.report.wall_total_s * 1e3, 1),
            "compact_matches_fresh_build": compact_ok,
        }
        rows.append(row)
        out(f"smoke/mutation insert_ef={ief}: "
            f"{row['insert_rate']:.0f} inserts/s, "
            f"staleness={row['staleness']:.3f}, recall "
            f"{row['pre_compact_recall_at_1']:.3f} (tombstoned) -> "
            f"{row['post_compact_recall_at_1']:.3f} (compacted), "
            f"compact==fresh: {compact_ok}")
    return rows


def _mean_steps(trace_comps) -> float:
    """Mean per-query effective step count from a cumulative-comps trace:
    the last scan step whose comparison counter still moved (+1 for the
    seed-scoring init step). Frozen/done rows stop moving — this is the
    column that shows term="stable" retiring rows early."""
    tc = np.asarray(trace_comps)
    changed = tc[1:] != tc[:-1]                       # (T-1, Q)
    last = np.where(changed.any(axis=0),
                    changed.shape[0] - 1 - changed[::-1].argmax(axis=0), -1)
    return float((last + 2).mean())


def _entry_term_sweep(searcher, queries, gt_k, ef: int, out) -> list[dict]:
    """Seeding x termination matrix over the main world (DESIGN.md §12).

    Rows: every entry in ENTRY_TERM_ENTRIES under term="fixed" at ef and
    term="stable" at ENTRY_TERM_EF_FACTOR*ef, plus one restarts>0 row.
    Walls time the FULL search — seeds inside the timer — so the hub
    shortlist scan vs hierarchy descent cost difference lands in wall_ms,
    not just in comps. check_regression reads three invariants off these
    rows: hubs matches hierarchy recall at equal (ef, term) with bounded
    wall, and per entry stable spends fewer comps than fixed at equal
    recall."""
    k = ENTRY_TERM_K
    configs = []
    for entry in ENTRY_TERM_ENTRIES:
        configs.append(SearchSpec(ef=ef, k=k, entry=entry))
        configs.append(SearchSpec(ef=ENTRY_TERM_EF_FACTOR * ef, k=k,
                                  entry=entry, term="stable",
                                  stable_steps=ENTRY_TERM_STABLE_STEPS))
    configs.append(SearchSpec(ef=ENTRY_TERM_EF_FACTOR * ef, k=k,
                              entry="hubs", term="stable",
                              stable_steps=ENTRY_TERM_STABLE_STEPS,
                              restarts=ENTRY_TERM_RESTARTS))
    rows = []
    q = queries.shape[0]
    for spec in configs:
        wall, res = timeit(lambda: searcher.search(queries, spec), iters=3)
        _, _, tc = searcher.search_with_trace(queries, spec)
        ids = np.asarray(res.ids[:, :k])
        hits = sum(len(set(ids[i]) & set(gt_k[i])) for i in range(q))
        row = {
            "entry": spec.entry,
            "term": spec.term,
            "ef": spec.ef,
            "k": k,
            "stable_steps": (spec.stable_steps if spec.term == "stable"
                             else None),
            "restarts": spec.restarts,
            "recall_at_k": round(hits / (q * k), 4),
            "comps_per_query": round(float(res.n_comps.mean()), 1),
            "wall_ms": round(wall * 1e3, 2),
            "qps": round(q / wall, 1),
            "mean_steps": round(_mean_steps(tc), 1),
        }
        rows.append(row)
        out(f"smoke/entry_term {row['entry']}/{row['term']}"
            f"{'+r' + str(row['restarts']) if row['restarts'] else ''} "
            f"ef={row['ef']}: recall@{k}={row['recall_at_k']:.3f} "
            f"comps={row['comps_per_query']:.0f} "
            f"steps={row['mean_steps']:.0f} wall={row['wall_ms']:.1f}ms")
    return rows


def _pq_sweep(key, n: int, q: int, ef: int, out) -> list[dict]:
    """Quantization-ladder recall/comps/bytes across d (DESIGN.md §8, §15),
    same n as the main world so the committed rows stay comparable with the
    perf guard. Every row runs exact / sq8 / pq through the same graph and
    seeds, records ``*_bytes_per_query`` (the §15 bandwidth column — the
    ladder must be monotone exact > sq8 > pq), then an OPQ twin: a second
    engine over the SAME graph with an OPQ-trained table attached, so the
    opq-vs-pq contrast is purely the learned rotation. d >= 64 rows are
    labeled ``regime="high_d"``: that is where the pq recall gap opens and
    where OPQ must close at least half of it (the §15 acceptance bar).

    The sweep draws its own query pool of at least 240 regardless of the
    main world's ``q``: recall@1 granularity is 1/q, and the gap-closed
    gate divides two recall deltas — at q=80 the d=128 gap is ~4 queries
    and the quotient is sampling noise (observed 0.00 and 0.78 across
    seeds for the same tables)."""
    import jax.numpy as jnp

    from repro.baselines.pq import build_opq, derive_opq_key
    from repro.core import bruteforce as bf

    q = max(q, 240)
    rows = []
    for i, (sd, M) in enumerate(PQ_SWEEP):
        kw = jax.random.fold_in(key, 200 + i)
        # anisotropic world: decaying per-dim scales under a random rotation
        # (QR of a gaussian) — the axis-aligned subspace split that plain PQ
        # uses is deliberately misaligned with the data's true axes
        scales = 1.0 / jnp.sqrt(1.0 + jnp.arange(sd, dtype=jnp.float32))
        rot = jnp.linalg.qr(
            jax.random.normal(jax.random.fold_in(kw, 7), (sd, sd))
        )[0]
        sbase = (jax.random.normal(kw, (n, sd)) * scales) @ rot
        squeries = (jax.random.normal(jax.random.fold_in(kw, 1), (q, sd))
                    * scales) @ rot
        g = bf.exact_knn_graph(sbase, 16)
        gd = diversify.build_gd_graph(sbase, g)
        s = Searcher.from_graph(sbase, gd, key=kw)
        gt = bf.ground_truth(squeries, sbase, 1)
        row = {"n": n, "d": sd, "pq_m": M,
               "regime": "high_d" if sd >= 64 else "low_d",
               "bytes_per_vec_exact": 4 * sd, "bytes_per_vec_sq8": sd,
               "bytes_per_vec_pq": M,
               "mem_ratio_pq": round(4 * sd / M, 1)}
        spec = None
        for scorer in ("exact", "sq8", "pq"):
            # random entries: comps then measure pure traversal work, so the
            # scorer comparison-count contrast is not drowned by the
            # projection seeder's O(n*m/d) scan charge
            spec = SearchSpec(ef=ef, k=1, entry="random", scorer=scorer,
                              pq_m=M)
            wall, res = timeit(lambda: s.search(squeries, spec), iters=3)
            row[f"{scorer}_recall_at_1"] = round(
                float((res.ids[:, 0] == gt[:, 0]).mean()), 4
            )
            row[f"{scorer}_comps_per_query"] = round(
                float(res.n_comps.mean()), 1
            )
            row[f"{scorer}_wall_ms"] = round(wall * 1e3, 2)
            row[f"{scorer}_bytes_per_query"] = round(
                float(res.bytes_touched.mean()), 1
            )
        # the OPQ twin: same base, same graph, same seeds — only the code
        # table differs (rotation learned by alternating PQ / Procrustes)
        s_opq = Searcher.from_graph(
            sbase, gd, key=kw,
            pq=build_opq(sbase, M=M, key=derive_opq_key(kw)),
        )
        wall, res = timeit(lambda: s_opq.search(squeries, spec), iters=3)
        row["opq_recall_at_1"] = round(
            float((res.ids[:, 0] == gt[:, 0]).mean()), 4)
        row["opq_comps_per_query"] = round(float(res.n_comps.mean()), 1)
        row["opq_wall_ms"] = round(wall * 1e3, 2)
        gap = row["exact_recall_at_1"] - row["pq_recall_at_1"]
        row["pq_recall_gap"] = round(gap, 4)
        row["opq_gap_closed"] = (
            round((row["opq_recall_at_1"] - row["pq_recall_at_1"])
                  / gap, 4) if gap > 1e-9 else None
        )
        rows.append(row)
        out(f"smoke/pq d={sd} M={M} [{row['regime']}] "
            f"mem {row['mem_ratio_pq']}x: recall "
            f"exact={row['exact_recall_at_1']:.3f} "
            f"sq8={row['sq8_recall_at_1']:.3f} "
            f"pq={row['pq_recall_at_1']:.3f} "
            f"opq={row['opq_recall_at_1']:.3f} "
            f"(gap {row['pq_recall_gap']:.3f}, "
            f"opq closes {row['opq_gap_closed']}), bytes/q "
            f"{row['exact_bytes_per_query']:.0f}>"
            f"{row['sq8_bytes_per_query']:.0f}>"
            f"{row['pq_bytes_per_query']:.0f}")
    return rows


def _stream_sweep(key, ef: int, tile_q: int, out) -> list[dict]:
    rows = []
    for i, (sq, sn, sd) in enumerate(STREAM_SWEEP):
        kw = jax.random.fold_in(key, 100 + i)
        sbase = jax.random.uniform(kw, (sn, sd))
        squeries = jax.random.uniform(jax.random.fold_in(kw, 1), (sq, sd))
        g = bruteforce.exact_knn_graph(sbase, 16)
        gd = diversify.build_gd_graph(sbase, g)
        s = Searcher.from_graph(sbase, gd, key=kw)
        spec = SearchSpec(ef=ef, k=1, entry="projection")
        mono, res_m = timeit(lambda: s.search(squeries, spec), iters=3)
        stream, res_s = timeit(
            lambda: s.search_stream(squeries, spec, tile_q=tile_q), iters=3
        )
        gt = bruteforce.ground_truth(squeries, sbase, 1)
        rows.append({
            "q": sq, "n": sn, "d": sd, "tile_q": tile_q,
            "mono_ms": round(mono * 1e3, 2),
            "stream_ms": round(stream * 1e3, 2),
            "mono_qps": round(sq / mono, 1),
            "stream_qps": round(sq / stream, 1),
            "recall_at_1": round(
                float((res_s.ids[:, 0] == gt[:, 0]).mean()), 4
            ),
        })
        out(f"smoke/stream q={sq} n={sn} d={sd}: mono={mono*1e3:.1f}ms "
            f"stream={stream*1e3:.1f}ms recall={rows[-1]['recall_at_1']:.3f}")
    return rows


def _filtered_sweep(searcher, base, queries, ef: int, out) -> list[dict]:
    """Filtered-search trajectory (DESIGN.md §14) on the main world: recall
    vs a masked brute-force oracle, isolation violations, comps and wall
    per (selectivity, scorer, placement). Attaches a throwaway timestamp
    column to the main searcher — runs LAST so no other sweep sees it."""
    from repro.core.engine import filtered_brute_cutoff
    from repro.core.filters import FilterSpec

    n = base.shape[0]
    q = queries.shape[0]
    ts = np.random.default_rng(42).random(n).astype(np.float32)
    searcher.metadata = {"timestamp": ts}
    base_np = np.asarray(base)

    def overlap(ids, oracle):
        ids = np.asarray(ids)
        return sum(len(set(ids[i][ids[i] >= 0]) & set(oracle[i]))
                   for i in range(q)) / oracle.size

    gt_k = np.asarray(bruteforce.ground_truth(queries, base, FILTERED_K))
    rows = []
    for scorer, placement in FILTERED_COMBOS:
        spec = SearchSpec(ef=FILTERED_EF_FACTOR * ef, k=FILTERED_K,
                          entry="random", scorer=scorer,
                          base_placement=placement)
        if scorer == "pq":
            searcher.pq_index(spec)
        key = jax.random.fold_in(searcher.key, 600)
        unf = overlap(searcher.search(queries, spec, key).ids, gt_k)
        for sel in FILTERED_SELECTIVITIES:
            fspec = spec._replace(filter=FilterSpec(time_range=(0.0, sel)))
            wall, res = timeit(
                lambda: searcher.search(queries, fspec, key), iters=3)
            allow = ts <= sel
            ids = np.asarray(res.ids)
            violations = int((~allow[ids[ids >= 0]]).sum())
            oracle = np.nonzero(allow)[0][np.asarray(bruteforce.ground_truth(
                queries, jax.numpy.asarray(base_np[allow]), FILTERED_K))]
            rec = overlap(ids, oracle)
            brute = int(allow.sum()) <= filtered_brute_cutoff(fspec)
            row = {
                "sel": sel, "scorer": scorer, "placement": placement,
                "n_allowed": int(allow.sum()),
                "path": "brute" if brute else "graph",
                "recall_at_k": round(rec, 4),
                "unfiltered_recall_at_k": round(unf, 4),
                "recall_ratio": round(rec / max(unf, 1e-9), 4),
                "violations": violations,
                "comps_per_query": round(float(res.n_comps.mean()), 1),
                "wall_ms": round(wall * 1e3, 2),
            }
            rows.append(row)
            out(f"smoke/filtered sel={sel} {scorer}/{placement} "
                f"[{row['path']}]: recall={rec:.3f} (unfiltered {unf:.3f}), "
                f"violations={violations}, comps={row['comps_per_query']:.0f}")
    return rows


def run(n: int = 8000, d: int = 16, q: int = 100, ef: int = 48,
        stream_tile: int = 128, out_path: str = "BENCH_engine.json",
        host_tier_ns: list[int] | None = None, out=print) -> dict:
    key = jax.random.PRNGKey(0)
    base = jax.random.uniform(key, (n, d))
    queries = jax.random.uniform(jax.random.fold_in(key, 1), (q, d))
    gt = bruteforce.ground_truth(queries, base, 1)

    searcher = Searcher.build(base, key=key, with_hierarchy=True)
    report = {"n": n, "d": d, "q": q, "ef": ef, "strategies": {}}
    for entry in sorted(ENTRY_STRATEGIES):
        spec = SearchSpec(ef=ef, k=1, entry=entry)
        wall, res = timeit(lambda: searcher.search(queries, spec), iters=3)
        recall = float((res.ids[:, 0] == gt[:, 0]).mean())
        comps = float(res.n_comps.mean())
        report["strategies"][entry] = {
            "recall_at_1": round(recall, 4),
            "comps_per_query": round(comps, 1),
            "wall_ms": round(wall * 1e3, 2),
            "qps": round(q / wall, 1),
        }
        out(f"smoke/engine/{entry},recall={recall:.3f},comps={comps:.0f},"
            f"wall_ms={wall*1e3:.1f}")

    # beam-core batched timing at a fixed spec — the hot-loop perf tracker.
    # Seeds are drawn outside the timer: entry='random' seed generation is
    # O(Q*n) (see ROADMAP) and would otherwise dominate the number.
    spec = SearchSpec(ef=ef, k=1, entry="random")
    ent, extra = searcher.seed(queries, spec)
    wall, _ = timeit(
        lambda: searcher.search(queries, spec, entries=ent, entry_comps=extra),
        iters=5,
    )
    report["beam_core_wall_ms"] = round(wall * 1e3, 2)

    # the compressed twin: same seeds, pq-scored traversal + exact rerank
    # (code table trained off the timer; LUT build is part of serving cost)
    pq_spec = SearchSpec(ef=ef, k=1, entry="random", scorer="pq")
    searcher.pq_index(pq_spec)
    wall, _ = timeit(
        lambda: searcher.search(queries, pq_spec, entries=ent,
                                entry_comps=extra),
        iters=5,
    )
    report["pq_beam_wall_ms"] = round(wall * 1e3, 2)

    # streaming-vs-monolithic trajectory over (Q, n, d) — DESIGN.md §7
    report["streaming"] = _stream_sweep(key, ef, stream_tile, out)

    # exact-vs-pq recall/comps/memory across d — DESIGN.md §8
    report["pq_sweep"] = _pq_sweep(key, n, q, ef, out)

    # construct × diversify build trajectory over the main world — §10
    report["build_sweep"] = _build_sweep(
        base, queries, gt, ef, jax.random.fold_in(key, 400), out
    )

    # seeding x termination matrix over the main world — DESIGN.md §12
    gt_k = np.asarray(bruteforce.ground_truth(queries, base, ENTRY_TERM_K))
    report["entry_term_sweep"] = _entry_term_sweep(
        searcher, queries, gt_k, ef, out
    )

    # open-loop served latency vs offered QPS — DESIGN.md §11. Same world,
    # same random-entry spec as the beam-core tracker, ragged requests cut
    # from the main query pool: the served-vs-closed-batch recall/comps
    # columns are bit-comparable by construction.
    report.update(serving_sweep(searcher, spec, np.asarray(queries),
                                np.asarray(gt), out=out))

    # insert/delete/compact lifecycle per insert_ef — DESIGN.md §13
    report["mutation_sweep"] = _mutation_sweep(key, q, ef, out)

    # device/host/disk base placement at growing n — DESIGN.md §9, §15; a
    # sweep point at the main n reuses the world built above
    report["host_tier_sweep"] = _host_tier_sweep(
        key, host_tier_ns or [n], q, ef, out,
        main_world=(n, searcher, queries, gt),
    )

    # filtered search: selectivity x scorer x placement — DESIGN.md §14.
    # Runs last: it attaches a metadata column to the main searcher.
    report["filtered_sweep"] = _filtered_sweep(searcher, base, queries, ef,
                                               out)

    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    out(f"smoke/engine written to {out_path}")
    return report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=8000)
    ap.add_argument("--d", type=int, default=16)
    ap.add_argument("--q", type=int, default=100)
    ap.add_argument("--ef", type=int, default=48)
    ap.add_argument("--stream-tile", type=int, default=128)
    ap.add_argument("--out", default="BENCH_engine.json")
    ap.add_argument("--host-tier-ns", default="",
                    help="comma-separated n values for the tiered-base sweep "
                         "(default: the main world's --n; nightly CI passes "
                         "6000,60000,200000)")
    args = ap.parse_args()
    enable_compile_cache()
    tier_ns = ([int(v) for v in args.host_tier_ns.split(",") if v]
               if args.host_tier_ns else None)
    run(n=args.n, d=args.d, q=args.q, ef=args.ef,
        stream_tile=args.stream_tile, out_path=args.out,
        host_tier_ns=tier_ns)


if __name__ == "__main__":
    main()
