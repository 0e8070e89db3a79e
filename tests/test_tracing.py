"""The program's own names in a profiler trace: the ``jax.named_scope``s of
the beam search and of NN-Descent (carried in each HLO instruction's
``op_name``), and the ``ann.`` host spans of the server and the build. The
names change no computation: served answers are bit-identical with the
profiler on and off."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import beam_search as bs
from repro.core import nndescent
from repro.core.build import BuildSpec, GraphBuilder
from repro.core.engine import Searcher, SearchSpec
from repro.core.scorers import build_sq8
from repro.launch.server import AnnServer, ServeConfig

BEAM_SCOPES = ("beam_init", "beam_select", "beam_expand", "beam_score",
               "beam_visited", "beam_merge", "beam_freeze", "beam_finalize")
ND_SCOPES = ("nd_join", "nd_score", "nd_merge")


def op_names(lowered) -> list:
    txt = lowered.as_text(dialect="hlo", debug_info=True)
    return re.findall(r'op_name="([^"]+)"', txt)


def scopes_in(names) -> set:
    return {part for name in names for part in name.split("/")}


@pytest.fixture(scope="module")
def graph_world():
    n, d, Q = 2048, 16, 4    # n above the one-hot gather's limit
    key = jax.random.PRNGKey(3)
    base = jax.random.normal(key, (n, d))
    nbrs = jax.random.randint(jax.random.fold_in(key, 1), (n, 12), 0, n)
    queries = jax.random.normal(jax.random.fold_in(key, 2), (Q, d))
    entries = bs.random_entries(jax.random.fold_in(key, 3), n, Q, 8)
    return base, nbrs, queries, entries


@pytest.mark.parametrize("scorer", ["exact", "sq8"])
def test_beam_search_hlo_carries_every_scope(graph_world, scorer):
    base, nbrs, queries, entries = graph_world
    state = None
    if scorer == "sq8":
        sq = build_sq8(base)
        state = (sq.codes, sq.scale, sq.mn)
    lowered = bs.beam_search.lower(
        queries, base, nbrs, entries, ef=16, k=4, scorer=scorer,
        scorer_state=state, term="stable", restarts=1,
        restart_keys=jax.random.split(jax.random.PRNGKey(0), 4))
    names = op_names(lowered)
    assert set(BEAM_SCOPES) <= scopes_in(names)
    # the loop's phases sit inside the while body; the bit test of the
    # visited bitmap, under the kernels' own scope, inside the scorer call
    body = [n for n in names if "/while/body/" in n]
    for scope in BEAM_SCOPES[1:7]:
        assert any(f"/{scope}/" in n for n in body), scope
    assert any(re.search(r"/beam_score/(.*/)?visited_test/", n)
               for n in body)
    assert not any(re.search(r"/beam_score/(.*/)?beam_visited/", n)
                   for n in names)


def test_traverse_and_trace_share_the_step_scopes(graph_world):
    base, nbrs, queries, entries = graph_world
    sq = build_sq8(base)
    state = (sq.codes, sq.scale, sq.mn)
    for lowered in (
            bs.beam_traverse.lower(queries, nbrs, entries, ef=16,
                                   scorer="sq8", scorer_state=state),
            bs.search_with_trace.lower(queries, base, nbrs, entries, ef=16,
                                       k=4, max_steps=4)):
        assert set(BEAM_SCOPES[:6]) <= scopes_in(op_names(lowered))


def test_nndescent_round_hlo_carries_every_scope():
    n, d = 300, 8
    cfg = nndescent.NNDescentConfig(k=8, sample=4, sample_nn=4, reverse=8,
                                    chunk=128)
    base = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    ids = jnp.zeros((n, cfg.k), jnp.int32)
    dists = jnp.zeros((n, cfg.k))
    isnew = jnp.ones((n, cfg.k), bool)
    names = op_names(nndescent._round_jit.lower(
        base, ids, dists, isnew, jax.random.PRNGKey(1), cfg, "l2"))
    assert set(ND_SCOPES) <= scopes_in(names)
    init = op_names(nndescent._init_jit.lower(base, jax.random.PRNGKey(2),
                                              cfg, "l2"))
    assert "nd_init" in scopes_in(init)


# -- host spans --------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    key = jax.random.PRNGKey(9)
    base = jax.random.uniform(key, (1500, 16))
    queries = np.asarray(jax.random.uniform(jax.random.fold_in(key, 1),
                                            (24, 16)), np.float32)
    searcher = Searcher.build(base, key=key)
    return searcher, SearchSpec(ef=32, k=4, entry="random"), queries


def serve(searcher, spec, queries, sizes):
    """Serve requests of ``sizes`` rows with fixed keys, open loop."""
    server = AnnServer(searcher, spec,
                       ServeConfig(buckets=(1, 2, 4, 8), max_live_batches=2))
    lo = 0
    for i, sz in enumerate(sizes):
        server.submit(queries[lo:lo + sz],
                      jax.random.fold_in(searcher.key, 700 + i))
        server.poll()
        lo += sz
    server.drain()
    return sorted(server.completed, key=lambda r: r.rid)


def host_spans(trace_dir) -> list:
    """[(name, start, end, stats)] of the ``ann.`` spans in a trace."""
    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats))
                       for e in line.events if e.name.startswith("ann."))
    return out


SIZES = (1, 3, 2, 8, 1, 5)


@pytest.fixture(scope="module")
def traced_serve(served, tmp_path_factory):
    searcher, spec, queries = served
    serve(searcher, spec, queries, SIZES)     # compile outside the trace
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(trace_dir)
    try:
        reqs = serve(searcher, spec, queries, SIZES)
    finally:
        jax.profiler.stop_trace()
    return reqs, host_spans(trace_dir)


def test_server_spans_carry_each_requests_id(traced_serve):
    reqs, spans = traced_serve
    rids = sorted(r.rid for r in reqs)
    for name in ("ann.submit", "ann.admit", "ann.seed", "ann.pad",
                 "ann.dispatch", "ann.retire", "ann.wait", "ann.fetch"):
        assert sorted(s["rid"] for n, _, _, s in spans if n == name) == \
            rids, name
    buckets = {r.rid: r.bucket for r in reqs}
    for n, _, _, s in spans:
        if n in ("ann.admit", "ann.retire", "ann.fetch"):
            assert s["bucket"] == buckets[s["rid"]]
    assert any(n == "ann.poll" for n, _, _, _ in spans)


def test_admit_nests_in_poll_or_submit(traced_serve):
    _, spans = traced_serve

    def inside(child, parents):
        _, a, b, _ = child
        return any(pa <= a and b <= pb for n, pa, pb, _ in spans
                   if n in parents)

    for sp in spans:
        if sp[0] == "ann.admit":
            assert inside(sp, ("ann.poll", "ann.submit"))
        if sp[0] in ("ann.seed", "ann.pad", "ann.dispatch"):
            assert inside(sp, ("ann.admit",))
        if sp[0] in ("ann.wait", "ann.fetch"):
            assert inside(sp, ("ann.retire",))


def test_answers_bit_identical_with_the_profiler_on(served, traced_serve):
    searcher, spec, queries = served
    traced, _ = traced_serve
    plain = serve(searcher, spec, queries, SIZES)
    assert [r.rid for r in plain] == [r.rid for r in traced]
    for a, b in zip(plain, traced):
        np.testing.assert_array_equal(a.ids, b.ids)
        np.testing.assert_array_equal(a.dists, b.dists)
        np.testing.assert_array_equal(a.n_comps, b.n_comps)
        assert int(a.n_steps) == int(b.n_steps)


def test_request_keeps_the_searchs_step_count(served):
    searcher, spec, queries = served
    reqs = serve(searcher, spec, queries, SIZES)
    lo = 0
    for i, (req, sz) in enumerate(zip(reqs, SIZES)):
        assert isinstance(req.n_steps, jax.Array)   # no host copy
        direct = searcher.search(jnp.asarray(queries[lo:lo + sz]), spec,
                                 jax.random.fold_in(searcher.key, 700 + i))
        assert int(req.n_steps) == int(direct.n_steps) > 0
        lo += sz


def test_build_spans(tmp_path):
    base = jax.random.normal(jax.random.PRNGKey(5), (400, 8))
    spec = BuildSpec(construct="nndescent", diversify="gd", graph_k=8,
                     nd_rounds=2, proxy_sample=0, lid_sample=0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = GraphBuilder(spec).build(base, key=jax.random.PRNGKey(6))
    finally:
        jax.profiler.stop_trace()
    spans = host_spans(str(tmp_path))
    names = [n for n, _, _, _ in spans]
    assert names.count("ann.construct") == names.count("ann.diversify") == 1
    rounds = [s["round"] for n, _, _, s in spans if n == "ann.nd_round"]
    assert sorted(rounds) == list(range(res.report.rounds))
    (_, c0, c1, _), = [sp for sp in spans if sp[0] == "ann.construct"]
    assert all(c0 <= a and b <= c1 for n, a, b, _ in spans
               if n == "ann.nd_round")
