#!/usr/bin/env python3
"""Bring-up smoke of the graph-ANN main path on a TPU.

    python chip_smoke.py              # one chip: build -> check -> search -> serve
    python chip_smoke.py --chips 4    # four chips: sharded search vs emulation

One process, data made from ``--seed``. With no options it runs, on one chip
and at SIFT1M size (10^6 x 128, l2):

  (a) device check: the platform must be ``tpu`` and the kernels ``native``;
  (b) build: NN-Descent, then GD, then PQ, through ``GraphBuilder``;
  (c) the three masked gather kernels run natively on real beam inputs and
      are compared with their ``kernels/ref.py`` oracles;
  (d) ``Searcher.search`` with the exact, sq8 and pq scorers (k=10, ef=128),
      recall@10 against brute force run on the chip;
  (e) ragged requests through ``AnnServer``, answers equal to direct search.

``--chips 4`` runs only the shard-and-merge path (``distributed_search``
over ``make_flat_mesh()``), compared with ``emulated_shard_search`` on one
device over the same shards and with brute force.

Each phase prints its wall time and the part of it spent compiling, which is
set-up, not speed. This is a bring-up check, not a benchmark. The last line
is one JSON object naming the device; any failed phase exits non-zero before
printing it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SIFT1M_N = 1_000_000
EXACT_RECALL_FLOOR = 0.90
# native-vs-ref agreement: |d_native - d_ref| <= DIST_RTOL * scale + 1e-6,
# scale = |q|^2 + |x|^2 for l2 (the expanded-norm form's cancellation bound)
# and the sum of |LUT| entries for ADC
DIST_RTOL = 1e-5


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class Phase:
    """Times one phase: wall seconds and backend-compile seconds inside it."""

    def __init__(self, name: str, stats):
        self.name, self.stats = name, stats

    def __enter__(self):
        print(f"[{self.name}] start", flush=True)
        self.t0, self.c0 = time.perf_counter(), self.stats.compile_s
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            wall = time.perf_counter() - self.t0
            comp = self.stats.compile_s - self.c0
            print(f"[{self.name}] done: wall {wall:.3f} s, of which compile "
                  f"{comp:.3f} s", flush=True)
        return False


def import_repro():
    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        raise SmokeFailure(
            f"cannot import the repro package from {HERE}/src: {e}; run "
            "chip_smoke.py from a checkout of the repository") from e


def device_check(stats, chips: int):
    """Phase (a): a TPU with ``chips`` devices and native kernels, or fail."""
    import jax

    from repro.kernels import ops
    from repro.launch.runtime import device_summary

    with Phase("a/device", stats):
        devs = jax.devices()
        dev = devs[0]
        print(f"[a/device] jax {jax.__version__}, devices {devs}")
        print(f"[a/device] platform={dev.platform} kind={dev.device_kind} "
              f"count={len(devs)} kernel_mode={ops._mode()}")
        check(dev.platform == "tpu",
              f"no TPU found: JAX sees platform {dev.platform!r} "
              f"({len(devs)} device(s)); this smoke runs only on a TPU")
        check(len(devs) >= chips,
              f"--chips {chips} needs {chips} TPU devices, found {len(devs)}")
        check(ops._mode() == "native",
              f"kernel mode is {ops._mode()!r}, not 'native' (REPRO_PALLAS="
              f"{os.environ.get('REPRO_PALLAS')!r}); unset REPRO_PALLAS")
    return device_summary()


def make_corpus(args):
    import jax

    from repro.data.synthetic import make_ann_dataset

    if args.n != SIFT1M_N:
        print(f"CUT: n={args.n} instead of SIFT1M's {SIFT1M_N} "
              f"(--n); d=128 and l2 unchanged", flush=True)
    base, queries, metric = make_ann_dataset(
        "SIFT1M", key=jax.random.PRNGKey(args.seed),
        scale=args.n / SIFT1M_N, n_queries=args.queries)
    jax.block_until_ready((base, queries))
    print(f"[corpus] SIFT1M stand-in: base {base.shape} {base.dtype}, "
          f"queries {queries.shape}, metric {metric}, "
          f"{base.nbytes / 2**20:.1f} MiB on {base.devices()}")
    return base, queries, metric


def build(base, metric, seed, stats):
    """Phase (b): NN-Descent -> GD -> PQ through GraphBuilder."""
    import jax

    from repro.core.build import BuildSpec, GraphBuilder

    spec = BuildSpec(construct="nndescent", diversify="gd", compress="pq",
                     metric=metric, graph_k=20, pq_m=8)
    with Phase("b/build", stats):
        res = GraphBuilder(spec).build(base, key=jax.random.PRNGKey(seed + 1),
                                       verbose=True)
        rep = res.report
        print(f"[b/build] construct (nndescent) {rep.wall_construct_s:.3f} s "
              f"in {rep.rounds} rounds, converged={rep.converged}, "
              f"updates {list(rep.update_curve)}")
        print(f"[b/build] diversify (gd) {rep.wall_diversify_s:.3f} s, "
              f"compress (pq M=8) {rep.wall_compress_s:.3f} s, "
              f"total {rep.wall_total_s:.3f} s")
        print(f"[b/build] graph-recall proxy {rep.graph_recall_proxy}, "
              f"degree {rep.degree}, dropped reverse edges "
              f"{rep.dropped_reverse_edges}, LID {rep.lid}")
        check(rep.graph_recall_proxy >= 0.5,
              f"graph-recall proxy {rep.graph_recall_proxy} < 0.5: the "
              "NN-Descent graph is broken")
    return res


def _agree(name, got, want, scale):
    import numpy as np

    gd, gi = (np.asarray(x) for x in got)
    wd, wi = (np.asarray(x) for x in want)
    check(np.array_equal(gi, wi), f"{name}: native ids differ from ref "
          f"({int((gi != wi).sum())} of {gi.size})")
    fin = np.isfinite(wd)
    check(np.array_equal(fin, np.isfinite(gd)),
          f"{name}: +inf entries differ between native and ref")
    err = np.abs(gd[fin] - wd[fin])
    tol = DIST_RTOL * np.broadcast_to(scale, wd.shape)[fin] + 1e-6
    worst = float((err / tol).max()) if err.size else 0.0
    print(f"[c/kernels] {name}: ids equal ({int(fin.sum())} scored, "
          f"{int((~fin).sum())} masked), max |native-ref| "
          f"{float(err.max()) if err.size else 0.0:.3e}, worst err/tol "
          f"{worst:.3f}")
    check(worst <= 1.0, f"{name}: native and ref distances disagree beyond "
          f"{DIST_RTOL} relative tolerance (worst err/tol {worst:.3f})")


def kernel_check(searcher, queries, stats):
    """Phase (c): native kernels vs the ref oracles on real beam inputs —
    each query's seed vertex expanded, its seeds already visited."""
    import jax
    import jax.numpy as jnp

    from repro.baselines.pq import build_adc_luts
    from repro.core.beam_search import _mark_visited
    from repro.kernels import ref
    from repro.kernels.gather_adc import gather_adc_masked
    from repro.kernels.gather_distance import gather_distance_masked
    from repro.kernels.gather_sq8 import gather_sq8_masked

    spec = searcher.spec(ef=128, k=10)
    with Phase("c/kernels", stats):
        ent, _ = searcher.seed(queries, spec)
        n = searcher.neighbors.shape[0]
        visited = _mark_visited(
            jnp.zeros((queries.shape[0], (n + 31) // 32), jnp.uint32), ent)
        ids = searcher.neighbors[jnp.maximum(ent[:, 0], 0)]       # (Q, R)
        # the seeds' own rows again: guaranteed visited hits
        ids = jnp.concatenate([ids, ent], axis=1)
        rows = searcher.base[jnp.maximum(ids, 0)]
        scale = (jnp.sum(queries * queries, 1)[:, None]
                 + jnp.sum(rows * rows, 2))
        _agree("gather_distance_masked",
               gather_distance_masked(queries, ids, searcher.base, visited),
               ref.gather_distance_masked_ref(queries, ids, searcher.base,
                                              visited), scale)
        sq = searcher.sq8_index()
        _agree("gather_sq8_masked",
               gather_sq8_masked(queries, ids, sq.codes, sq.scale, sq.mn,
                                 visited),
               ref.gather_sq8_masked_ref(queries, ids, sq.codes, sq.scale,
                                         sq.mn, visited), scale)
        pq = searcher.pq_index(spec._replace(scorer="pq"))
        luts = build_adc_luts(queries, pq.codebooks, searcher.metric)
        lut_scale = jnp.sum(jnp.abs(luts), axis=(1, 2))[:, None] / luts.shape[2]
        _agree("gather_adc_masked",
               gather_adc_masked(ids, pq.codes, luts, visited),
               ref.gather_adc_masked_ref(ids, pq.codes, luts, visited),
               lut_scale)
        jax.block_until_ready(visited)


def search_check(searcher, queries, gt, stats):
    """Phase (d): recall@10 per scorer at ef=128 against brute force."""
    import jax
    import numpy as np

    recalls = {}
    for scorer in ("exact", "sq8", "pq"):
        spec = searcher.spec(ef=128, k=10, scorer=scorer)
        with Phase(f"d/search-{scorer}", stats):
            t0 = time.perf_counter()
            res = searcher.search(queries, spec)
            jax.block_until_ready(res.ids)
            first = time.perf_counter() - t0
            t0 = time.perf_counter()
            res = searcher.search(queries, spec)
            jax.block_until_ready(res.ids)
            warm = time.perf_counter() - t0
            ids = np.asarray(res.ids)
            hit = (ids[:, :, None] == gt[:, None, :]).any(-1).mean()
            recalls[scorer] = float(hit)
            print(f"[d/search-{scorer}] Q={queries.shape[0]} k=10 ef=128: "
                  f"recall@10 {hit:.4f}, mean comps "
                  f"{float(np.asarray(res.n_comps).mean()):.1f}, steps "
                  f"{int(res.n_steps)}, first call {first:.3f} s, warm call "
                  f"{warm:.3f} s")
    check(recalls["exact"] >= EXACT_RECALL_FLOOR,
          f"exact recall@10 {recalls['exact']:.4f} < {EXACT_RECALL_FLOOR}")
    return recalls


def serve_check(searcher, queries, seed, stats):
    """Phase (e): ragged requests through AnnServer == direct search."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.server import AnnServer, ServeConfig

    spec = searcher.spec(ef=128, k=10)
    rng = np.random.default_rng(seed)
    q = np.asarray(queries, np.float32)
    reqs = []
    for i in range(40):
        sz = int(rng.choice((1, 2, 3, 5, 8, 13)))
        lo = int(rng.integers(0, q.shape[0] - sz + 1))
        reqs.append((q[lo:lo + sz], jax.random.fold_in(searcher.key, 500 + i)))
    with Phase("e/serve", stats):
        server = AnnServer(searcher, spec, ServeConfig(buckets=(1, 2, 4, 8, 16),
                                                       max_live_batches=4,
                                                       max_queue_depth=64))
        server.warmup()
        t0 = time.perf_counter()
        for rows, key in reqs:
            server.submit_wait(rows, key)
        server.drain()
        served_s = time.perf_counter() - t0
        st = server.stats()
        check(len(server.completed) == len(reqs) and not server.shed,
              f"served {len(server.completed)} of {len(reqs)} requests, "
              f"{len(server.shed)} shed")
        for req in server.completed:
            rows, key = reqs[req.rid]
            direct = searcher.search(jnp.asarray(rows), spec, key)
            check(np.array_equal(req.ids, np.asarray(direct.ids)),
                  f"request {req.rid}: served ids differ from direct search")
        print(f"[e/serve] {len(reqs)} ragged requests "
              f"({sum(r[0].shape[0] for r in reqs)} query rows) served in "
              f"{served_s:.3f} s closed loop, p50 {st.get('p50_ms')} ms, "
              f"p99 {st.get('p99_ms')} ms; every answer equals direct search")


def one_chip(args, stats):
    import numpy as np

    from repro.core import bruteforce
    from repro.core.engine import Searcher

    base, queries, metric = make_corpus(args)
    res = build(base, metric, args.seed, stats)
    searcher = Searcher.from_build(base, res)
    with Phase("d/ground-truth", stats):
        gt = np.asarray(bruteforce.ground_truth(queries, base, 10, metric))
    kernel_check(searcher, queries, stats)
    recalls = search_check(searcher, queries, gt, stats)
    serve_check(searcher, queries, args.seed, stats)
    print(f"[summary] recall@10 at ef=128: {recalls}")


def device_bytes(dev) -> tuple[int, int]:
    """(bytes in use, peak bytes in use) of one device, as it reports them."""
    ms = dev.memory_stats()
    check(ms is not None, f"{dev} reports no memory stats")
    return ms["bytes_in_use"], ms.get("peak_bytes_in_use", -1)


def four_chips(args, stats):
    """Shard-and-merge over a 4-device mesh vs one-device emulation."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import bruteforce
    from repro.core.build import BuildSpec
    from repro.core.engine import SearchSpec, emulated_shard_search, \
        shard_entries
    from repro.distributed.sharded_ann import distributed_search, shard_build
    from repro.launch.mesh import make_flat_mesh

    base, queries, metric = make_corpus(args)
    mesh = make_flat_mesh()
    P_ = mesh.devices.size
    check(P_ == 4, f"make_flat_mesh() spans {P_} devices, not 4")
    with Phase("4/build-shards", stats):
        sb = shard_build(base, P_, spec=BuildSpec(
            construct="nndescent", diversify="gd", metric=metric,
            graph_k=20, proxy_sample=0, lid_sample=0),
            key=jax.random.PRNGKey(args.seed + 1))
        jax.block_until_ready(sb.nbr_shards)
        print(f"[4/build-shards] {P_} shards of {sb.base_shards.shape[1]} "
              f"rows, walls {[r.wall_total_s for r in sb.reports]} s")
    with Phase("4/ground-truth", stats):
        gt = np.asarray(bruteforce.ground_truth(
            queries, base[:P_ * sb.base_shards.shape[1]], 10, metric))
    spec = SearchSpec(ef=128, k=10, metric=metric)
    ent = shard_entries(jax.random.PRNGKey(args.seed + 2), P_,
                        queries.shape[0], sb.base_shards.shape[1], 8)
    live = jnp.ones((P_,), bool)
    with Phase("4/emulated", stats):
        ed, ei = emulated_shard_search(queries, sb.base_shards, sb.nbr_shards,
                                       ent, live, spec)
        ed, ei = np.asarray(ed), np.asarray(ei)
    with Phase("4/distributed", stats):
        shard = NamedSharding(mesh, P("shards"))
        bs = jax.device_put(sb.base_shards, shard)
        ns = jax.device_put(sb.nbr_shards, shard)
        es = jax.device_put(ent, shard)
        ls = jax.device_put(live, shard)
        qs = jax.device_put(queries, NamedSharding(mesh, P()))
        homes = {s.device for s in bs.addressable_shards}
        check(len(homes) == P_, f"base shards live on {len(homes)} devices")
        out = distributed_search(qs, bs, ns, es, ls, ef=spec.ef, k=spec.k,
                                 metric=metric, mesh=mesh)
        jax.block_until_ready(out)
        dd, di, comps = (np.asarray(x) for x in out)
        check(len(out[1].sharding.device_set) == P_,
              f"merged answers are on {len(out[1].sharding.device_set)} "
              "devices, not all four")
        shard_bytes = bs.addressable_shards[0].data.nbytes
        for dev in mesh.devices.flat:
            used, peak = device_bytes(dev)
            print(f"[4/distributed] {dev}: bytes_in_use {used}, peak {peak}")
            check(used >= shard_bytes, f"{dev} holds {used} bytes, less than "
                  f"its {shard_bytes}-byte base shard")
    check(np.array_equal(di, ei),
          f"sharded ids differ from the one-device emulation "
          f"({int((di != ei).sum())} of {di.size})")
    check(np.allclose(dd, ed, rtol=1e-5, atol=1e-5),
          "sharded distances differ from the one-device emulation")
    recall = float((di[:, :, None] == gt[:, None, :]).any(-1).mean())
    print(f"[4/summary] {P_}-device shard-and-merge equals the one-device "
          f"emulation over the same shards; recall@10 vs brute force "
          f"{recall:.4f}, mean comps {float(comps.mean()):.1f}")
    check(recall >= EXACT_RECALL_FLOOR,
          f"sharded recall@10 {recall:.4f} < {EXACT_RECALL_FLOOR}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the one-chip main path; 4: sharded search only")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the corpus, queries, build and requests")
    ap.add_argument("--n", type=int, default=SIFT1M_N,
                    help="base rows; below SIFT1M's 10^6 only as a printed "
                         "cut")
    ap.add_argument("--queries", type=int, default=256,
                    help="queries scored against brute force")
    args = ap.parse_args(argv)
    try:
        import_repro()
        from repro.launch.runtime import CompileStats, enable_compile_cache

        cache_dir = enable_compile_cache()
        stats = CompileStats()
        device = device_check(stats, args.chips)
        print(f"[cache] persistent compile cache at {cache_dir}")
        t0 = time.perf_counter()
        if args.chips == 4:
            four_chips(args, stats)
        else:
            one_chip(args, stats)
        print(f"[cache] {stats.hits} hits, {stats.misses} misses, "
              f"{stats.compile_s:.3f} s in backend compiles; total wall "
              f"{time.perf_counter() - t0:.3f} s")
        for name, count, secs in stats.top():
            print(f"[cache] compile {secs:9.3f} s in {count:4d} x {name}")
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
