"""The harness end to end on the CPU at a tiny size: the result line, the
refusal without a chip, and cells, mixes and metrics found by name."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench.harness import BENCH, ROOT

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run(harness, root, cell, trace=False, seconds=0.5, seed=2**31 + 5):
    return harness.run_cell(root, cell, seed, seconds, trace,
                            time.monotonic())


@pytest.mark.parametrize("cell", ["sift1m.batch", "sift1m.single_open",
                                  "sift1m.build"])
def test_result_line_has_the_contract_keys(cpu_harness, tiny_root, cell):
    line, checks = run(cpu_harness, tiny_root, cell)
    assert set(line) == CONTRACT_KEYS | {"checks"}
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, checks
    bench = json.load(open(os.path.join(tiny_root, "BENCHMARK.json")))
    e2e, _ = cpu_harness.cell_metrics(bench, cell)
    assert set(line["metrics"]) == {m["name"] for m in e2e}
    for m in e2e:
        assert line["metrics"][m["name"]]["unit"] == m["unit"]
        assert line["metrics"][m["name"]]["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert [c.split()[1] for c in checks] == list(line["checks"])
    json.dumps(line)


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "sift1m.batch", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "{" not in proc.stdout


def test_without_the_program_exits_nonzero(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "bench" / "run.py"), "--workload",
         "sift1m.batch", "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def test_new_files_are_found_by_name(cpu_harness, tiny_root, tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as files,
    with one new cell in BENCHMARK.json and no other edit, run."""
    root = str(tmp_path / "co")
    shutil.copytree(tiny_root, root)
    cfg = json.load(open(os.path.join(root, "bench", "configs",
                                      "sift1m.json")))
    cfg.update(name="sift1m_ragged", ef=24)
    with open(os.path.join(root, "bench", "configs", "sift1m_ragged.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "bench", "traffic", "ragged_open.json"),
              "w") as f:
        json.dump({"driver": "serve", "loop": "open", "rate_per_s": 30,
                   "sizes": [1, 2, 3], "warm_requests": 1,
                   "check_sample": 64}, f)
    with open(os.path.join(root, "bench", "metrics", "rows_per_request.py"),
              "w") as f:
        f.write("def read(run):\n"
                "    r = run.record['requests']\n"
                "    return sum(x['rows'] for x in r) / len(r)\n")
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({"name": "sift1m_ragged", "source": "test",
                             "file": "bench/configs/sift1m_ragged.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "sift1m.ragged_open",
                               "config": "sift1m_ragged",
                               "traffic": "ragged_open", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][1]["workloads"].append("sift1m.ragged_open")
    bench["per_layer"].append({"name": "rows_per_request", "unit": "rows",
                               "better": "higher", "source": "host_clock",
                               "layer": "server front end",
                               "moves": "p50_ms",
                               "workloads": ["sift1m.ragged_open"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    line, checks = run(cpu_harness, root, "sift1m.ragged_open")
    assert line["correct"] is True, checks
    assert set(line["metrics"]) == {"p50_ms", "recall_at_10", "setup_s"}
    line, checks = run(cpu_harness, root, "sift1m.ragged_open", trace=True,
                       seconds=0.3)
    assert line["correct"] is True, checks
    assert line["metrics"]["rows_per_request"]["value"] == pytest.approx(2.0,
                                                                         abs=0.5)
    assert "queue_wait_ms.open" not in line["metrics"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
