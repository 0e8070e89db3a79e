"""Serving launcher: autoregressive decode loop (LM archs), batched retrieval
scoring (recsys archs), or graph-ANN query serving (``--arch ann``) on the
production mesh.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b --smoke \
        --tokens 32 --batch 2
    PYTHONPATH=src python -m repro.launch.serve --arch ann --smoke \
        --entry projection --batch 64 --batches 8
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.launch.runtime import device_summary, enable_compile_cache
from repro.launch.mesh import make_production_mesh, make_test_mesh


def serve_open_loop(searcher, spec, args, key) -> None:
    """``--serve``: ragged Poisson request traffic through the continuous-
    batching server (launch/server.py) instead of pre-formed equal batches —
    bucketed compiled cores, admission cap, queue-depth shedding, p50/p99
    over per-request enqueue->complete latency (DESIGN.md §11)."""
    import numpy as np

    from repro.core import bruteforce
    from repro.launch.server import AnnServer, ServeConfig

    try:
        from benchmarks.loadgen import (make_requests, poisson_arrivals,
                                        run_open_loop)
    except ImportError as e:
        raise SystemExit(
            "--serve drives benchmarks/loadgen.py; run from the repo root "
            "(PYTHONPATH=src python -m repro.launch.serve ...) so the "
            "benchmarks package is importable"
        ) from e

    sizes = tuple(int(s) for s in args.request_sizes.split(","))
    config = ServeConfig(
        buckets=tuple(int(b) for b in args.serve_buckets.split(",")),
        max_live_batches=args.max_live_batches,
        max_queue_depth=args.queue_depth,
    )
    server = AnnServer(searcher, spec, config)
    d_dim = searcher.base.shape[1]
    pool = np.asarray(
        jax.random.normal(jax.random.fold_in(key, 11), (256, d_dim)),
        np.float32,
    )
    requests = make_requests(pool, args.serve_requests, sizes, seed=0,
                             base_key=jax.random.fold_in(searcher.key, 777))
    server.warmup()   # compile one beam core per bucket off the timed path

    mean_size = sum(r.rows.shape[0] for r in requests) / len(requests)
    arrivals = poisson_arrivals(args.serve_qps / mean_size, len(requests),
                                seed=0)
    run_open_loop(server, requests, arrivals)
    st = server.stats()

    # recall/comps over the actual served traffic (ground truth off the
    # timed path, shed requests excluded — they never produced answers)
    gt = np.asarray(
        bruteforce.ground_truth(pool, searcher.base, 1, searcher.metric)
    )
    hits = rows = comps = 0
    for req in server.completed:
        g = gt[requests[req.rid].start:
               requests[req.rid].start + req.ids.shape[0], 0]
        hits += int((req.ids[:, 0] == g).sum())
        rows += req.ids.shape[0]
        comps += float(req.n_comps.sum())
    print(f"[serve-ann] open loop: offered {args.serve_qps:.0f} qps over "
          f"{len(requests)} requests (sizes {sizes}), buckets "
          f"{config.buckets}, {config.max_live_batches} live / "
          f"{config.max_queue_depth} queued max")
    print(f"[serve-ann] served {st['completed']} requests "
          f"({st['shed']} shed): p50={st.get('p50_ms')} ms "
          f"p90={st.get('p90_ms')} ms p99={st.get('p99_ms')} ms, "
          f"queue wait {st.get('mean_queue_ms')} ms, sustained "
          f"{st.get('sustained_qps')} qps, fill {st['mean_fill']}, "
          f"buckets {st['bucket_counts']}")
    print(f"[serve-ann] served recall@1={hits / max(rows, 1):.3f}, "
          f"comps/query={comps / max(rows, 1):.0f}")

    if not getattr(args, "serve_mutate", 0):
        return

    # --serve-mutate: mutate the index under the live server, hot-swap it in
    # (warmup pre-flip), and push a second request stream through the SAME
    # server instance — DESIGN.md §13's serving side.
    from repro.core.mutable import MutableIndex

    n_ins = args.serve_mutate
    n0 = searcher.base.shape[0]
    midx = MutableIndex(np.asarray(searcher.base, np.float32),
                        np.asarray(searcher.neighbors),
                        metric=searcher.metric, key=searcher.key,
                        insert_ef=32, diversify="gd")
    t_m = time.monotonic()
    midx.insert_batch(np.asarray(
        jax.random.normal(jax.random.fold_in(key, 21), (n_ins, d_dim)),
        np.float32,
    ))
    dead = np.random.default_rng(0).choice(n0, size=max(n_ins // 2, 1),
                                           replace=False)
    midx.delete(dead)
    mutate_s = time.monotonic() - t_m
    version = server.swap(midx.searcher(),
                          key=jax.random.fold_in(key, 23))
    ev = server.swap_events[-1]
    print(f"[serve-ann] hot-swap v{version}: +{n_ins} inserts "
          f"({midx.insert_rate:.0f} pts/s) -{len(dead)} tombstones in "
          f"{mutate_s:.2f}s, staleness={midx.staleness:.3f}; warm+flip "
          f"{ev['warm_s']:.2f}s with {ev['live_at_flip']} live / "
          f"{ev['queued_at_flip']} queued at the flip")
    done0, shed0 = st["completed"], st["shed"]
    requests2 = make_requests(pool, args.serve_requests, sizes, seed=1,
                              base_key=jax.random.fold_in(searcher.key, 778))
    run_open_loop(server, requests2,
                  poisson_arrivals(args.serve_qps / mean_size,
                                   len(requests2), seed=1))
    st2 = server.stats()
    dead_set = set(int(i) for i in dead)
    dead_hits = sum(int(i) in dead_set
                    for req in server.completed[done0:]
                    for i in req.ids.ravel())
    print(f"[serve-ann] post-swap stream: "
          f"{st2['completed'] - done0} served "
          f"({st2['shed'] - shed0} shed), p99={st2.get('p99_ms')} ms "
          f"cumulative, tombstoned ids in answers: {dead_hits} "
          f"(must be 0)")


def serve_ann(args) -> None:
    """ANN serving family: load an index artifact (or build one through the
    ``core.build`` pipeline and save it), then answer batched query streams
    through the SearchEngine with the chosen entry strategy. The same
    `Searcher.search` call serves every strategy."""
    from repro.core import bruteforce
    from repro.core import io as index_io
    from repro.core.build import BuildSpec, GraphBuilder
    from repro.core.engine import Searcher, SearchSpec

    key = jax.random.PRNGKey(0)
    index_path = index_io.normalize_path(args.index) if args.index else None
    save_path = (index_io.normalize_path(args.save_index)
                 if args.save_index else index_path)
    if index_path and os.path.exists(index_path):
        art = index_io.load_index(index_path)
        searcher = art.to_searcher()
        layers = 0 if art.hierarchy is None else art.hierarchy.num_layers
        print(f"[serve-ann] loaded artifact {index_path} (v{art.version}): "
              f"n={art.n} d={art.d} metric={art.metric} layers={layers} "
              f"pq={'yes' if art.pq is not None else 'no'}")
        if args.entry == "hierarchy" and searcher.hierarchy is None:
            raise SystemExit(
                "--entry hierarchy: this artifact has no hierarchy; rebuild "
                "with --build-construct hnsw --save-index " + index_path
            )
        if args.save_index and save_path != index_path:
            # re-save the loaded artifact (migrates legacy v0 flat .npz
            # files to the current manifest format)
            p = index_io.save_index(save_path,
                                    index_io.IndexArtifact.from_searcher(
                                        searcher, art.provenance))
            print(f"[serve-ann] re-saved loaded index to {p} "
                  f"(schema v{index_io.ARTIFACT_VERSION})")
    else:
        n, d = (20_000, 32) if args.smoke else (1_000_000, 64)
        base = jax.random.normal(key, (n, d))
        construct = args.build_construct
        if construct == "auto":
            construct = "hnsw" if args.entry == "hierarchy" else "nndescent"
        diversify = args.diversify
        if diversify is None:
            diversify = "none" if construct == "hnsw" else "gd"
        bspec = BuildSpec(
            construct=construct, diversify=diversify,
            compress="pq" if args.scorer == "pq" else "none",
            metric="l2", graph_k=args.build_k, nd_rounds=args.build_rounds,
            pq_m=args.pq_m,
        )
        result = GraphBuilder(bspec).build(base, key=key)
        searcher = Searcher.from_build(base, result, key=key)
        rep = result.report
        print(f"[serve-ann] built {bspec.construct}·{bspec.diversify}·"
              f"{bspec.compress} over n={n} d={d} in {rep.wall_total_s:.1f}s "
              f"(rounds={rep.rounds}, graph-recall~{rep.graph_recall_proxy}, "
              f"degree mean={rep.degree['mean']}, "
              f"dropped reverse={rep.dropped_reverse_edges})")
        if save_path:
            p = index_io.save_index(
                save_path,
                index_io.IndexArtifact.from_build(base, result, metric="l2",
                                                  key=key),
            )
            print(f"[serve-ann] saved index artifact to {p} "
                  f"(hierarchy and PQ persist: reloads skip both rebuild "
                  f"and k-means)")

    spec = SearchSpec(ef=args.ef, k=args.topk, metric=searcher.metric,
                      entry=args.entry, r_tile=args.r_tile,
                      scorer=args.scorer, pq_m=args.pq_m, rerank=args.rerank,
                      base_placement=args.base_placement,
                      store_dtype=args.store_dtype,
                      term=args.term, stable_steps=args.stable_steps,
                      restarts=args.restarts)
    if args.base_placement != "device" and args.scorer == "exact":
        raise SystemExit(f"--base-placement {args.base_placement} traverses "
                         "device-resident compressed codes; add --scorer pq "
                         "or --scorer sq8")
    if args.base_placement != "device":
        # the float base moves off-device up front; from here the device
        # only ever sees the code table, adjacency, and per-batch rerank rows
        store = searcher.base_store(args.base_placement, args.store_dtype)
        print(f"[serve-ann] base {args.base_placement}-resident "
              f"({args.store_dtype}): {store.nbytes / 2**20:.1f} MiB "
              f"off-device; device keeps codes + adjacency")
    if args.scorer == "pq":
        t0 = time.time()
        attached = searcher.pq
        idx = searcher.pq_index(spec)
        source = ("attached" if attached is not None
                  and (attached.M, attached.K) == (idx.M, idx.K)
                  else "trained at startup")
        d_dim = searcher.base.shape[1]
        print(f"[serve-ann] pq scorer ready in {time.time()-t0:.1f}s "
              f"({source}): M={idx.M} K={idx.K} ({idx.M} B/vector vs "
              f"{4*d_dim} B exact, {4*d_dim/idx.M:.0f}x smaller scored base)")
    # --stream-tile T splits each incoming batch into T-row tiles that
    # pipeline through one compiled beam core (DESIGN.md §7); 0 = monolithic.
    if args.stream_tile:
        do_search = lambda q, k: searcher.search_stream(
            q, spec, k, tile_q=args.stream_tile
        )
    else:
        do_search = lambda q, k: searcher.search(q, spec, k)
    d_dim = searcher.base.shape[1]
    qkey = jax.random.fold_in(key, 7)
    warm = jax.random.normal(qkey, (args.batch, d_dim))
    res = do_search(warm, qkey)                  # compile + strategy prep
    jax.block_until_ready(res.ids)

    if args.serve:
        serve_open_loop(searcher, spec, args, qkey)
        return

    # the query stream is materialized (and blocked on) BEFORE t0, for both
    # batch and stream modes — reported qps measures search, not the
    # jax.random.normal synthesis that used to run inside the timer
    stream = [
        jax.random.normal(jax.random.fold_in(qkey, b), (args.batch, d_dim))
        for b in range(args.batches)
    ]
    skeys = [jax.random.fold_in(qkey, 1000 + b) for b in range(args.batches)]
    jax.block_until_ready(stream)

    t0 = time.time()
    served_ids, served_comps, served = [], [], 0
    for q, kb in zip(stream, skeys):
        res = do_search(q, kb)
        jax.block_until_ready(res.ids)
        served += args.batch
        served_ids.append(res.ids[:, 0])
        served_comps.append(res.n_comps)
    dt = time.time() - t0
    # recall/comps over the actual served traffic (ground truth computed off
    # the timed path)
    all_q = jnp.concatenate(stream)
    gt = bruteforce.ground_truth(all_q, searcher.base, 1, searcher.metric)
    recall = float((jnp.concatenate(served_ids) == gt[:, 0]).mean())
    comps = float(jnp.concatenate(served_comps).mean())
    mode = (f"stream[{args.stream_tile}]" if args.stream_tile else "batch")
    print(f"[serve-ann] entry={args.entry} ef={args.ef} k={args.topk} "
          f"mode={mode}: {served} queries in {dt*1e3:.0f} ms "
          f"({served/dt:.0f} qps), recall@1={recall:.3f}, "
          f"comps/query={comps:.0f}")
    if args.base_placement != "device":
        store = searcher.base_store(args.base_placement, args.store_dtype)
        print(f"[serve-ann] {args.base_placement} tier: "
              f"{store.gathered_bytes / max(served, 1) / 1024:.1f} KiB "
              f"gathered/query ({store.gathered_rows} rerank rows "
              f"total) vs {store.nbytes / 2**20:.1f} MiB base kept "
              f"off-device")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--entry", default="random",
                    help="[ann] entry strategy: "
                         "random|projection|hierarchy|lsh|hubs")
    ap.add_argument("--ef", type=int, default=64, help="[ann] beam width")
    ap.add_argument("--term", default="fixed", choices=["fixed", "stable"],
                    help="[ann] per-query termination: fixed = run until the "
                         "classic done condition; stable = freeze a row once "
                         "its top-k stops improving for --stable-steps steps")
    ap.add_argument("--stable-steps", type=int, default=8,
                    help="[ann] --term stable patience window (steps)")
    ap.add_argument("--restarts", type=int, default=0,
                    help="[ann] GNNS-style fresh-seed restarts per query on "
                         "early convergence (comps charged to the query)")
    ap.add_argument("--topk", type=int, default=10, help="[ann] answers/query")
    ap.add_argument("--batches", type=int, default=8,
                    help="[ann] query batches to serve")
    ap.add_argument("--index", default=None,
                    help="[ann] index-artifact .npz to load (or save after "
                         "build); flat, hierarchical and PQ state all "
                         "round-trip (core/io.py)")
    ap.add_argument("--save-index", default=None,
                    help="[ann] write the built artifact here (defaults to "
                         "--index when that file does not exist yet)")
    ap.add_argument("--build-construct", default="auto",
                    choices=["auto", "nndescent", "exact", "hnsw",
                             "incremental"],
                    help="[ann] construct stage of the build pipeline "
                         "(auto = hnsw for --entry hierarchy, else "
                         "nndescent; incremental = streaming inserts "
                         "through MutableIndex, DESIGN.md §13)")
    ap.add_argument("--build-k", type=int, default=20,
                    help="[ann] raw k-NN degree out of the construct stage")
    ap.add_argument("--build-rounds", type=int, default=15,
                    help="[ann] NN-Descent round budget")
    ap.add_argument("--diversify", default=None,
                    choices=["none", "gd", "dpg"],
                    help="[ann] diversify stage (default: gd; none for "
                         "hnsw constructs)")
    ap.add_argument("--r-tile", type=int, default=0,
                    help="[ann] gather-kernel neighbor tile (0 = default)")
    ap.add_argument("--scorer", default="exact",
                    help="[ann] per-hop scorer: exact|sq8|pq (sq8/pq = "
                         "compressed traversal + exact rerank)")
    ap.add_argument("--pq-m", type=int, default=8,
                    help="[ann] PQ sub-vectors = code bytes/vector")
    ap.add_argument("--rerank", type=int, default=0,
                    help="[ann] exact-reranked survivors under --scorer pq "
                         "(0 = all ef)")
    ap.add_argument("--stream-tile", type=int, default=0,
                    help="[ann] split batches into this many queries per "
                         "streamed tile (0 = one monolithic search per batch)")
    ap.add_argument("--base-placement", default="device",
                    choices=["device", "host", "disk"],
                    help="[ann] where the float base lives (DESIGN.md §9/§15)"
                         ": host/disk keep only compressed codes + adjacency "
                         "on device and gather rerank rows from the tier "
                         "(needs --scorer pq or sq8)")
    ap.add_argument("--store-dtype", default="f32", choices=["f32", "bf16"],
                    help="[ann] residual storage dtype for host/disk tiers "
                         "(bf16 = half the rerank bandwidth, DESIGN.md §15)")
    ap.add_argument("--serve", action="store_true",
                    help="[ann] open-loop serving mode (DESIGN.md §11): "
                         "ragged Poisson request traffic through the "
                         "continuous-batching server instead of pre-formed "
                         "--batch x --batches blocks")
    ap.add_argument("--serve-qps", type=float, default=500.0,
                    help="[ann] offered load for --serve, query rows/s")
    ap.add_argument("--serve-requests", type=int, default=200,
                    help="[ann] requests in the offered stream")
    ap.add_argument("--serve-buckets", default="1,2,4,8,16",
                    help="[ann] sorted batch-size buckets; one compiled beam "
                         "core each, requests pad to the smallest that fits")
    ap.add_argument("--request-sizes", default="1,2,3,4,6,8",
                    help="[ann] ragged request sizes drawn by the loadgen")
    ap.add_argument("--max-live-batches", type=int, default=4,
                    help="[ann] admission cap: batches in flight at once")
    ap.add_argument("--queue-depth", type=int, default=16,
                    help="[ann] backlog bound; submits past it are shed")
    ap.add_argument("--serve-mutate", type=int, default=0,
                    help="[ann] under --serve: after the first request "
                         "stream, insert this many points and tombstone "
                         "half as many through MutableIndex, hot-swap the "
                         "mutated index into the live server (warmup "
                         "pre-flip, zero drops), then serve a second "
                         "stream against it (DESIGN.md §13)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.serve and args.arch != "ann":
        raise SystemExit("--serve is an --arch ann mode")
    if args.serve and args.stream_tile:
        raise SystemExit("--serve buckets requests itself; drop --stream-tile")

    if args.arch == "ann":
        serve_ann(args)
        print(json.dumps({"ok": True, "device": device_summary()}))
        return

    ad = configs.get_arch(args.arch)
    if args.smoke:
        ad = dataclasses.replace(ad, model_cfg=ad.smoke_cfg)
        mesh = make_test_mesh((1, 1))
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)

    if ad.family == "lm":
        from repro.models import transformer as tf

        cfg = ad.model_cfg
        params = tf.init_params(jax.random.PRNGKey(0), cfg)
        caches = tf.init_cache(cfg, args.batch, args.max_len)
        step = jax.jit(
            lambda p, t, pos, c: tf.decode_step(p, t, pos, c, cfg),
            donate_argnums=(3,),
        )
        tok = jnp.zeros((args.batch,), jnp.int32)
        t0 = time.time()
        with mesh:
            for t in range(args.tokens):
                logits, caches = step(params, tok,
                                      jnp.full((args.batch,), t, jnp.int32), caches)
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        dt = time.time() - t0
        print(f"[serve] {args.tokens} tokens x {args.batch} seqs in {dt:.2f}s "
              f"({args.tokens*args.batch/dt:.1f} tok/s)")
    else:
        from repro.core.diversify import build_gd_graph
        from repro.core.nndescent import NNDescentConfig, build_knn_graph
        from repro.models.recsys import retrieval_score_ann, retrieval_score_exact

        n, d = (20_000, 32) if args.smoke else (1_000_000, 64)
        key = jax.random.PRNGKey(0)
        items = jax.random.normal(key, (n, d))
        queries = jax.random.normal(jax.random.fold_in(key, 1), (args.batch, d))
        t0 = time.time()
        d_ex, i_ex = retrieval_score_exact(queries, items, k=10)
        jax.block_until_ready(i_ex)
        print(f"[serve] exact retrieval over {n}: {(time.time()-t0)*1e3:.1f} ms")
        g = build_knn_graph(items, NNDescentConfig(k=16, rounds=8), metric="ip")
        gd = build_gd_graph(items, g, metric="ip")
        t0 = time.time()
        d_a, i_a = retrieval_score_ann(queries, items, gd.neighbors, k=10, ef=96)
        jax.block_until_ready(i_a)
        hit = float((i_a[:, :1] == i_ex[:, :1]).mean())
        print(f"[serve] ANN retrieval: {(time.time()-t0)*1e3:.1f} ms "
              f"recall@1={hit:.3f}")


if __name__ == "__main__":
    main()
