"""The benchmark harness: one cell, one seed, one measured window.

``bench/run.py`` is the command; this module is what it runs, importable so
that the tests can drive a cell on the CPU with the chip check stubbed out.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``. Everything it
needs is found by name: ``bench/configs/<config>.json`` (the deployment),
``bench/traffic/<traffic>.json`` (the mix, which names its driver module
``bench/traffic/<driver>.py``) and ``bench/metrics/<metric>.py`` for each
per-layer metric. A driver has two functions: ``setup(h)`` builds or loads
what the window needs and warms exactly the shapes it will use, and
``window(h, state)`` runs the measured window and returns an
:class:`Outcome`. The harness then frees the program's state, compares a
sample of the answers with the reference (``check.py``) and prints the result
line.
"""
from __future__ import annotations

import contextlib
import gc
import hashlib
import importlib.util
import json
import os
import shutil
import sys
import time
import types
from dataclasses import dataclass

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

# Seconds of schedule that a traced run traces before it pauses, drains and
# stops the profiler; the rest of its window runs untraced.
TRACE_S = 3.0


class BenchError(Exception):
    """A run that cannot produce a result: it exits non-zero and prints none."""


@dataclass
class Outcome:
    attempted: int
    failed: int
    unanswered: int
    e2e: dict                       # end-to-end numbers the driver measured
    groups: list                    # [check.Group] answers to compare
    record: dict                    # what the per-layer readers read


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import a driver or metric file by its path (names may hold dots)."""
    if not os.path.exists(path):
        raise BenchError(f"{path} does not exist")
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path)[:-3].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str) -> tuple[list, list]:
    """The cell's end-to-end metrics, and the per-layer metrics read in it."""
    def listed(m):
        return "workloads" not in m or cell in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if listed(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


# the configuration's keys that fix its corpus and its index
INDEX_KEYS = ("n", "d", "metric", "dtype", "latent", "noise", "corpus_seed",
              "construct", "diversify", "graph_k", "nd_rounds")


def cache_key(config: dict) -> str:
    """Digest of what fixes a configuration's index: its corpus and build."""
    blob = json.dumps({k: config[k] for k in INDEX_KEYS}, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def require_chips(n: int) -> None:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX finds platform {devs[0].platform!r}; "
                         "this benchmark runs only on the chip")
    if len(devs) < n:
        raise BenchError(f"the cell needs {n} chips, JAX finds {len(devs)}")


def device_peaks(kind: str) -> dict:
    peaks = load_json(os.path.join(BENCH, "peaks.json"))
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def enable_compile_cache(root: str) -> None:
    """JAX's persistent cache at a fixed path inside the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` already names one."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        path = os.path.join(root, "bench", ".cache", "jax")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Harness:
    """What a driver sees: the cell, its files, the seed, the clock, spans."""

    def __init__(self, root, cell, config, traffic, seed, seconds, trace,
                 stats):
        self.root, self.cell = root, cell
        self.config, self.traffic = config, traffic
        self.seed, self.seconds, self.trace = seed, float(seconds), trace
        self.stats = stats
        self.cache = os.path.join(root, "bench", ".cache")
        self.trace_dir = os.path.join(self.cache, "trace", cell["name"])
        self.traced_once = False

    def note(self, line: str) -> None:
        """A line of the run's output before its result."""
        print(line, flush=True)

    def span(self, name: str):
        """A host span in the trace (a no-op in untraced runs)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("bench." + name)

    @contextlib.contextmanager
    def traced(self):
        """Profile the enclosed segment (a no-op in untraced runs)."""
        if not self.trace:
            yield
            return
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        # device activity and TraceAnnotations only: the Python tracer would
        # time every function call of the host loop
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.traced"):
                yield
        finally:
            jax.profiler.stop_trace()
            self.traced_once = True

    def searcher(self):
        """The configuration's index: loaded from ``bench/.cache/index``, or
        built through ``GraphBuilder`` and saved there by the first run."""
        import jax

        from bench import gen
        from repro.core import io
        from repro.core.build import GraphBuilder

        c = self.config
        path = os.path.join(self.cache, "index",
                            f"{c['name']}-{cache_key(c)}.npz")
        if not os.path.exists(path):
            t = time.perf_counter()
            base = gen.corpus(c)
            res = GraphBuilder(build_spec(c)).build(
                base, key=gen.jax_key(c["corpus_seed"], 7))
            os.makedirs(os.path.dirname(path), exist_ok=True)
            io.save_index(path, io.IndexArtifact.from_build(
                base, res, c["metric"], key=gen.jax_key(c["corpus_seed"], 8)))
            rep = res.report
            self.note(f"index: built {os.path.basename(path)} in "
                      f"{time.perf_counter() - t:.3f} s (construct "
                      f"{rep.wall_construct_s} s in {rep.rounds} rounds, "
                      f"diversify {rep.wall_diversify_s} s)")
            del base, res
        t = time.perf_counter()
        searcher = io.load_index(path).to_searcher()
        jax.block_until_ready(searcher.base)
        self.note(f"index: loaded {os.path.basename(path)} in "
                  f"{time.perf_counter() - t:.3f} s")
        return searcher

    def search_spec(self, searcher):
        c = self.config
        return searcher.spec(ef=c["ef"], k=c["k"], scorer=c["scorer"],
                             entry=c["entry"],
                             base_placement=c["base_placement"])


def build_spec(config: dict):
    from repro.core.build import BuildSpec

    c = config
    return BuildSpec(construct=c["construct"], diversify=c["diversify"],
                     compress="none", metric=c["metric"],
                     graph_k=c["graph_k"], nd_rounds=c["nd_rounds"])


def _peak_memory() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def load_cell(root: str, workload: str):
    """(BENCHMARK.json, cell, configuration, traffic, driver module)."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no cell {workload!r} in BENCHMARK.json; cells: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     cell["traffic"] + ".json"))
    driver = load_module(os.path.join(root, "bench", "traffic",
                                      traffic["driver"] + ".py"))
    return bench, cell, config, traffic, driver


def limits(config: dict) -> dict:
    return {"dist_err": config["dist_err_max"],
            "recall_at_10": config["recall_at_10_min"]}


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float) -> tuple[dict, list]:
    """One run of one cell. Returns (result line, check lines)."""
    from bench import check

    bench, cell, config, traffic, driver = load_cell(root, workload)
    e2e_specs, layer_specs = cell_metrics(bench, workload)

    require_chips(cell["chips"])
    import jax

    from repro.launch.runtime import CompileStats

    enable_compile_cache(root)
    stats = CompileStats()
    dev = jax.devices()[0]
    peaks = device_peaks(dev.device_kind)
    h = Harness(root, cell, config, traffic, seed, seconds, trace, stats)

    state = driver.setup(h)
    # objects made in set-up (JAX's own, the index, the warm-up) go to the
    # permanent generation: a full collection in the window then scans only
    # the window's objects instead of pausing the serving thread for ~0.1 s
    gc.collect()
    gc.freeze()
    setup_s = time.monotonic() - t_start
    n0, m0 = sum(c for c, _ in stats.by_name.values()), stats.misses
    out = driver.window(h, state)
    compiles = sum(c for c, _ in stats.by_name.values()) - n0
    h.note(f"window: {compiles} compiles ({stats.misses - m0} cache misses) "
           f"inside the window; set-up {setup_s:.3f} s")
    memory_peak = _peak_memory()
    del state
    gc.unfreeze()
    gc.collect()

    correct, numbers = check.judge(
        out.groups, out.unanswered, metric=config["metric"],
        k=config["k"], limits=limits(config),
        sample=traffic["check_sample"], seed=seed)
    recall = next(x.value for x in numbers if x.name == "recall_at_10")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    measured = dict(out.e2e, setup_s=setup_s, recall_at_10=recall)
    line = {"correct": bool(correct), "attempted": out.attempted,
            "failed": out.failed}
    if not trace:
        line["metrics"] = {m["name"]: {"value": measured[m["name"]],
                                       "unit": m["unit"]} for m in e2e_specs}
    else:
        from bench.trace import Trace

        tr = Trace.from_dir(h.trace_dir) if h.traced_once else None
        run = types.SimpleNamespace(record=out.record, trace=tr,
                                    config=config, traffic=traffic,
                                    peaks=peaks, cell=cell)
        line["metrics"] = {}
        for m in layer_specs:
            reader = load_module(os.path.join(root, "bench", "metrics",
                                              m["name"] + ".py"))
            value = reader.read(run)
            if value is not None:
                line["metrics"][m["name"]] = {"value": value,
                                              "unit": m["unit"]}
        if tr is not None:
            device["busy_s"] = tr.busy_s()
            device["window_s"] = tr.window_s()
            line["breakdown"] = {"device_ops": tr.top_ops(10),
                                 "idle_gaps": tr.idle_gaps(10)}
    line["device"] = device
    line["checks"] = {x.name: {"value": x.value, "limit": x.limit}
                      for x in numbers}
    return line, [x.line() for x in numbers]


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    t_start = time.monotonic() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line, checks = run_cell(ROOT, args.workload, args.seed, args.seconds,
                                bool(args.trace), t_start)
    except (BenchError, ImportError) as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    for c in checks:
        print(c, file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0
