#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``: the reference put
in the program's place, computed one precision lower than the configuration
states (bfloat16 for its float32), must come out not correct.

    python3 bench/control.py --workload sift1m.batch --seeds 11 12 13

For each seed it draws the query rows a run of the cell would send and the
same sample of them the run's check would draw, answers each with
``reference.exact_topk(..., dtype="bfloat16")`` and judges those answers
exactly as a run's are judged (``check.judge``). It prints one line per seed
and, last, one JSON object of the readings. The benchmark's own runs never
run it; ``bench/tests/test_bench_correctness.py`` runs it at a small size.
"""
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if sys.path and os.path.abspath(sys.path[0]) == BENCH:
    sys.path[0] = ROOT
else:
    sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "src"))
# the TPU runtime's logs stay inside the checkout
os.environ.setdefault("TPU_LOG_DIR", os.path.join(BENCH, ".cache", "tpu_logs"))


def control_run(root: str, workload: str, seed: int, seconds: float):
    """(correct, [check.Number]) of the bfloat16 reference in one run's
    place."""
    from bench import check, harness, reference

    _, cell, config, traffic, driver = harness.load_cell(root, workload)
    h = harness.Harness(root, cell, config, traffic, seed, seconds, False,
                        None)
    groups = []
    for corpus, rows in driver.control_inputs(h):
        q = rows[check.sample_rows(len(rows), traffic["check_sample"], seed)]
        ids, dists = reference.exact_topk(q, corpus(), config["k"],
                                          config["metric"], dtype="bfloat16")
        groups.append(check.Group(corpus, q, ids, dists))
    return check.judge(groups, 0, metric=config["metric"], k=config["k"],
                       limits=harness.limits(config),
                       sample=traffic["check_sample"], seed=seed)


def main(argv=None) -> int:
    import argparse
    import json

    from bench import harness

    ap = argparse.ArgumentParser(description="Run the control of a cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: run_seconds)")
    args = ap.parse_args(argv)
    seconds = args.seconds or harness.load_json(
        os.path.join(ROOT, "BENCHMARK.json"))["run_seconds"]
    try:
        harness.require_chips(1)
    except harness.BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    readings = {}
    for seed in args.seeds:
        correct, numbers = control_run(ROOT, args.workload, seed, seconds)
        print(f"control {args.workload} seed {seed}: correct {correct}; "
              + "; ".join(x.line() for x in numbers), flush=True)
        readings[seed] = {x.name: x.value for x in numbers}
        readings[seed]["correct"] = correct
    print(json.dumps({"workload": args.workload, "control": readings}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
