"""Driver of index builds: whole NN-Descent + GD builds, back to back.

Parameters (the traffic file): ``probe_queries`` fresh queries search each
built index at the configuration's search settings, and ``check_sample``
probe rows are compared with the reference.

Build ``i`` of a run indexes ``n`` (the configuration's corpus size) fresh
points on the configuration's manifold, drawn from (seed, i), with a key
drawn likewise. Builds start while the window's time is not up, so a run
overruns ``--seconds`` by at most one build. Set-up runs one whole build and
one probe search on a corpus no run measures, which compiles (or loads) every
program the window runs. A traced run traces its first build.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from bench import gen
from bench.check import Group
from bench.harness import Outcome, build_spec

WARM_SEED = 0


def _build_and_probe(h, spec, base, key, probes):
    import jax
    import jax.numpy as jnp

    from repro.core.build import GraphBuilder
    from repro.core.engine import Searcher

    with h.span("build"):
        t = time.perf_counter()
        res = GraphBuilder(spec).build(base, key=key)
        jax.block_until_ready(res.graph.neighbors)
        wall = time.perf_counter() - t
    with h.span("probe"):
        searcher = Searcher.from_build(base, res)
        out = searcher.search(jnp.asarray(probes), h.search_spec(searcher))
        ids, dists = np.asarray(out.ids), np.asarray(out.dists)
    return res.report, wall, ids, dists


def control_inputs(h):
    """[(corpus, query rows)] of the first build of a run."""
    c = h.config
    probes = np.asarray(gen.fresh_points(c, h.traffic["probe_queries"],
                                         h.seed, 11, 0))
    return [(lambda: gen.fresh_points(c, c["n"], h.seed, 10, 0), probes)]


def setup(h):
    c = h.config
    spec = build_spec(h.config)
    base = gen.fresh_points(c, c["n"], WARM_SEED, 10, 0)
    probes = np.asarray(gen.fresh_points(c, h.traffic["probe_queries"],
                                         WARM_SEED, 11, 0))
    _build_and_probe(h, spec, base, gen.jax_key(WARM_SEED, 12, 0), probes)
    return {"spec": spec}


def window(h, st):
    import jax

    c = h.config
    builds, groups = [], []
    t0 = time.monotonic()
    while not builds or time.monotonic() - t0 < h.seconds:
        i = len(builds)
        with h.span("corpus"):
            base = gen.fresh_points(c, c["n"], h.seed, 10, i)
            probes = np.asarray(gen.fresh_points(
                c, h.traffic["probe_queries"], h.seed, 11, i))
            jax.block_until_ready(base)
        first = i == 0 and h.trace
        with h.traced() if first else contextlib.nullcontext():
            rep, wall, ids, dists = _build_and_probe(
                h, st["spec"], base, gen.jax_key(h.seed, 12, i), probes)
        builds.append(dict(rows=int(base.shape[0]), wall_s=wall,
                           construct_s=rep.wall_construct_s,
                           diversify_s=rep.wall_diversify_s,
                           rounds=rep.rounds, converged=rep.converged,
                           traced=first))
        groups.append(Group(
            corpus=lambda i=i: gen.fresh_points(c, c["n"], h.seed, 10, i),
            queries=probes, ids=ids, dists=dists))
        del base
    rows = sum(b["rows"] for b in builds)
    wall = sum(b["wall_s"] for b in builds)
    h.note("build: " + ", ".join(
        f"{b['rows']} rows in {b['wall_s']:.3f} s ({b['rounds']} rounds, "
        f"construct {b['construct_s']} s, diversify {b['diversify_s']} s)"
        for b in builds))
    return Outcome(attempted=len(builds), failed=0, unanswered=0,
                   e2e={"build_rows_per_s": rows / wall}, groups=groups,
                   record={"builds": builds})
