"""Benchmark harness — one entry per paper table/figure.

CI scale by default (n~2e4); --full uses the paper's 1e6-1e7 sizes.
Output lines are `name,key=value,...` CSV-ish records, teed by the runner.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax

sys.path.insert(0, "src")

from repro.data.synthetic import make_ann_dataset  # noqa: E402
from repro.launch.runtime import enable_compile_cache  # noqa: E402

from . import (  # noqa: E402
    fig3_categories,
    fig4_hierarchy,
    fig5_diversification,
    fig6_comparisons,
    smoke,
    tab1_datasets,
)
from .bench_util import AnnWorld  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale datasets")
    ap.add_argument("--datasets", default="RAND10M4D,RAND10M32D,RAND1M,SIFT1M",
                    help="comma list from repro.data.synthetic.PAPER_DATASETS")
    ap.add_argument("--only", default=None,
                    help="comma list of benches: tab1,fig3,fig4,fig5,fig6,smoke")
    args = ap.parse_args()
    enable_compile_cache()
    scale_small = {"RAND10M4D": 2e-3, "RAND10M8D": 2e-3, "RAND10M16D": 2e-3,
                   "RAND10M32D": 2e-3, "RAND1M": 2e-2, "SIFT1M": 2e-2,
                   "GIST1M": 1e-2, "GLOVE1M": 2e-2}
    only = set(args.only.split(",")) if args.only else None

    def want(b):
        return only is None or b in only

    t0 = time.time()
    if want("smoke"):
        smoke.run()
    if want("tab1"):
        tab1_datasets.run(scale=1.0 if args.full else 0.002)

    for name in args.datasets.split(","):
        scale = 1.0 if args.full else scale_small[name]
        base, queries, metric = make_ann_dataset(name, scale=scale,
                                                 n_queries=100)
        print(f"# dataset {name}: n={base.shape[0]} d={base.shape[1]} "
              f"metric={metric} ({time.time()-t0:.0f}s)", flush=True)
        world = AnnWorld(base, queries, metric=metric)
        if want("fig3"):
            fig3_categories.run(world, name)
        if want("fig4"):
            fig4_hierarchy.run(world, name)
        if want("fig5"):
            fig5_diversification.run(world, name)
        if want("fig6"):
            fig6_comparisons.run(world, name)
        print(f"# done {name} ({time.time()-t0:.0f}s)", flush=True)


if __name__ == "__main__":
    main()
