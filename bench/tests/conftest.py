"""CPU tests of the benchmark: its yardstick, and its harness driven at a
tiny size with the look for a chip stubbed out."""
import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_N = 2000


def tiny_bench(dst: str) -> str:
    """A checkout-shaped copy of the benchmark at ``dst``: the real driver,
    metric and yardstick files, with each configuration cut to ``TINY_N``
    rows and each traffic mix to a few requests. Only data files differ."""
    shutil.copytree(BENCH, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__",
                                                  "tests"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for c in bench["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        cfg.update(n=TINY_N, ef=32)
        with open(os.path.join(dst, c["file"]), "w") as f:
            json.dump(cfg, f)
    tdir = os.path.join(dst, "bench", "traffic")
    for name in os.listdir(tdir):
        if not name.endswith(".json"):
            continue
        t = json.load(open(os.path.join(tdir, name)))
        t.update({k: v for k, v in dict(
            clients=2, rows_per_request=16, pool_rows=256, rate_per_s=40,
            warm_requests=1, probe_queries=64, check_sample=128).items()
            if k in t})
        with open(os.path.join(tdir, name), "w") as f:
            json.dump(t, f)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dst


@pytest.fixture
def cpu_harness(monkeypatch):
    """The harness with its chip check, peak table and compile cache
    stubbed for the CPU."""
    from bench import harness

    peaks = harness.load_json(os.path.join(BENCH, "peaks.json"))
    monkeypatch.setattr(harness, "require_chips", lambda n: None)
    monkeypatch.setattr(harness, "device_peaks",
                        lambda kind: peaks["TPU v5 lite"])
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: None)
    return harness


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return tiny_bench(str(tmp_path_factory.mktemp("checkout")))
