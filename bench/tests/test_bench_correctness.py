"""The comparison that decides ``correct`` fails what it must: the bfloat16
control, and runs with the timed path broken underneath."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, control, gen, harness, reference


@pytest.mark.parametrize("cell", ["sift1m.batch", "sift1m.build",
                                  "glove1m.batch"])
def test_control_is_not_correct(tiny_root, cell):
    correct, numbers = control.control_run(tiny_root, cell, 2**31 + 11, 0.5)
    assert not correct
    by = {x.name: x for x in numbers}
    assert not by["dist_err"].ok
    assert by["malformed"].ok and by["unanswered"].ok


@pytest.mark.parametrize("metric", ["l2", "cos"])
def test_reference_answers_are_correct(metric):
    cfg = dict(n=2000, d=100, latent=8, noise=0.01, corpus_seed=5)
    q = np.asarray(gen.fresh_points(cfg, 64, 1, 4))
    corpus = lambda: gen.corpus(cfg)  # noqa: E731
    ids, dists = reference.exact_topk(q, corpus(), 10, metric)
    correct, numbers = check.judge(
        [check.Group(corpus, q, ids, dists)], 0, metric=metric, k=10,
        limits={"dist_err": 1e-4, "recall_at_10": 0.9}, sample=64, seed=1)
    assert correct, [x.line() for x in numbers]


def _step_unchanged(monkeypatch):
    """The beam loop's step leaves its state as it was: no step runs."""
    from repro.core import engine

    real = engine.beam_search
    monkeypatch.setattr(engine, "beam_search",
                        lambda *a, **kw: real(*a, **dict(kw, max_steps=0)))


def _half_left_out(monkeypatch):
    """Half of the rows never searched: every other row of each batch, and
    every other request where a request is one row."""
    from repro.core import engine

    real = engine.beam_search
    calls = [0]

    def half(queries, *a, q_valid=None, **kw):
        keep = (jnp.arange(queries.shape[0]) + calls[0]) % 2 == 0
        calls[0] += 1
        if q_valid is not None:
            keep = keep & q_valid
        return real(queries, *a, q_valid=keep, **kw)

    monkeypatch.setattr(engine, "beam_search", half)


def _answer_altered(monkeypatch):
    """Each row's best id is replaced by its successor where it is made."""
    from repro.core import engine

    real = engine.beam_search

    def altered(queries, base, *a, **kw):
        res = real(queries, base, *a, **kw)
        ids = res.ids.at[:, 0].set((res.ids[:, 0] + 1) % base.shape[0])
        return res._replace(ids=ids)

    monkeypatch.setattr(engine, "beam_search", altered)


def _build_step_unchanged(monkeypatch):
    """Every NN-Descent round returns the graph it was given."""
    from repro.core import nndescent

    monkeypatch.setattr(
        nndescent, "_round_jit",
        lambda base, ids, dists, isnew, key, cfg, metric:
        (ids, dists, isnew, jnp.int32(0)))


def _build_half_left_out(monkeypatch):
    """The build indexes only the first half of the corpus's rows."""
    from repro.core import build

    real = build.GraphBuilder.build

    def half(self, base, *a, **kw):
        res = real(self, base, *a, **kw)
        n = base.shape[0]
        nb = res.graph.neighbors
        nb = jnp.where(nb >= n // 2, -1, nb).at[n // 2:].set(-1)
        return res._replace(graph=res.graph._replace(neighbors=nb))

    monkeypatch.setattr(build.GraphBuilder, "build", half)


FAULTS = {
    "sift1m.batch": [_step_unchanged, _half_left_out, _answer_altered],
    "sift1m.single_open": [_step_unchanged, _half_left_out,
                           _answer_altered],
    "sift1m.build": [_build_step_unchanged, _build_half_left_out,
                     _answer_altered],
}


@pytest.mark.parametrize("cell,fault", [(c, f) for c, fs in FAULTS.items()
                                        for f in fs],
                         ids=lambda x: getattr(x, "__name__", x))
def test_broken_timed_path_is_not_correct(cpu_harness, tiny_root,
                                          monkeypatch, cell, fault):
    fault(monkeypatch)
    line, checks = cpu_harness.run_cell(tiny_root, cell, 2**31 + 23, 0.5,
                                        False, time.monotonic())
    assert line["correct"] is False, checks
