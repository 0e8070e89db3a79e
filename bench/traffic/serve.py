"""Driver of served-search traffic through ``AnnServer.submit``/``poll``.

Parameters (the traffic file):

* ``loop``: ``closed`` - ``clients`` callers, each keeping one request of
  ``rows_per_request`` rows outstanding and sending the next when it is
  answered, until the window's time is up; or ``open`` - requests due at
  ``gen.arrivals(rate_per_s, seconds, seed)``, sent when due whether or not
  earlier ones were answered, with sizes drawn from ``sizes``.
* ``warm_requests``: requests of each size the window sends, served and
  drained in set-up so that every shape is compiled before the window.
* ``check_sample``: answered rows compared with the reference.

Query rows are fresh points on the corpus's manifold drawn from the seed;
the closed loop cycles through ``pool_rows`` of them.

Latency is timed from when a request was due (open loop) or sent (closed
loop) to when its answer is on the host. A traced run traces the first
``TRACE_S`` seconds of the schedule, drains, stops the profiler and resumes
the schedule shifted by that pause, so the pause delays no request.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from bench import gen
from bench.check import Group
from bench.harness import TRACE_S, Outcome

POLL_SLEEP_S = 5e-4


def _server_class():
    from repro.launch.server import AnnServer

    class Server(AnnServer):
        """Keeps each request's beam step count, a counter the server has
        and drops (read by ``beam_step_us``)."""

        def _retire(self, lb):
            super()._retire(lb)
            lb.request.n_steps = lb.result.n_steps

    return Server


def _rows(h):
    """(due times or None, request sizes, query rows) of a run."""
    t, c = h.traffic, h.config
    if t["loop"] == "closed":
        due = None
        sizes = np.array([t["rows_per_request"]])
        n_rows = t["pool_rows"]
    elif t["loop"] == "open":
        due = gen.arrivals(t["rate_per_s"], h.seconds, h.seed)
        sizes = gen.request_sizes(t["sizes"], len(due), h.seed)
        n_rows = int(sizes.sum())
    else:
        raise ValueError(f"unknown loop {t['loop']!r}")
    return due, sizes, np.asarray(gen.fresh_points(c, n_rows, h.seed, 4))


def control_inputs(h):
    """[(corpus, query rows)] a run of this cell draws its answers from."""
    config = h.config
    return [(lambda: gen.corpus(config), _rows(h)[2])]


def setup(h):
    import jax

    from repro.launch.server import ServeConfig

    t, c = h.traffic, h.config
    searcher = h.searcher()
    spec = h.search_spec(searcher)
    scfg = ServeConfig(buckets=tuple(c["buckets"]),
                       max_live_batches=c["max_live_batches"],
                       max_queue_depth=c["max_queue_depth"])
    due, sizes, pool = _rows(h)
    Server = _server_class()
    warm = Server(searcher, spec, scfg)
    for size in sorted({int(s) for s in sizes}):
        for _ in range(t["warm_requests"]):
            warm.submit_wait(pool[:size])
        warm.drain()
    jax.block_until_ready(searcher.base)
    return dict(server=Server(searcher, spec, scfg), due=due, sizes=sizes,
                pool=pool)


def _segments(h):
    """[(start, end, traced)] of schedule seconds."""
    if h.trace and h.seconds > TRACE_S:
        return [(0.0, TRACE_S, True), (TRACE_S, h.seconds, False)]
    return [(0.0, h.seconds, h.trace)]


def _closed(h, st, t0, a, b, sent):
    """Keep ``clients`` requests outstanding until the schedule (seconds
    since ``t0``) reaches ``b``, then drain. Returns the segment's
    requests."""
    server, pool = st["server"], st["pool"]
    size, clients = h.traffic["rows_per_request"], h.traffic["clients"]
    out, live = [], []

    def send():
        lo = (len(sent) * size) % (pool.shape[0] - size + 1)
        with h.span("submit"):
            req = server.submit(pool[lo:lo + size])
        req.due = req.t_enqueue
        sent.append(req)
        return req

    for _ in range(clients):
        live.append(send())
    while live:
        with h.span("poll"):
            server.poll()
        with h.span("sleep"):
            time.sleep(POLL_SLEEP_S)
        keep = []
        for req in live:
            if req.t_complete is None:
                keep.append(req)
                continue
            out.append(req)
            if time.monotonic() - t0 < b:
                keep.append(send())
        live = keep
    return out


def _open(h, st, t0, a, b, sent):
    """Send every request due in [a, b) at ``t0`` + its due time, whether or
    not earlier ones were answered, then drain."""
    server, pool, due, sizes = st["server"], st["pool"], st["due"], st["sizes"]
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    out = []
    for i in np.nonzero((due >= a) & (due < b))[0]:
        at = t0 + due[i]
        while True:
            dt = at - time.monotonic()
            if dt <= 0:
                break
            with h.span("poll"):
                server.poll()
            with h.span("sleep"):
                time.sleep(min(dt, POLL_SLEEP_S))
        rows = pool[offsets[i]:offsets[i + 1]]
        # behind schedule by more than a millisecond: enqueue without
        # driving admission, as the server's own load generator does
        with h.span("submit"):
            req = server.submit(rows, advance=dt > -1e-3)
        req.due = at
        sent.append(req)
        out.append(req)
    with h.span("drain"):
        server.drain()
    return out


def window(h, st):
    closed = h.traffic["loop"] == "closed"
    drive = _closed if closed else _open
    sent, traced = [], set()
    t0 = time.monotonic()  # schedule time 0
    for a, b, tr in _segments(h):
        # after a traced segment's pause the schedule resumes at ``a`` now
        t0 = max(t0, time.monotonic() - a)
        with h.traced() if tr else contextlib.nullcontext():
            reqs = drive(h, st, t0, a, b, sent)
        if tr:
            traced.update(r.rid for r in reqs)
    return _outcome(h, sent, traced, time.monotonic(), closed)


def _outcome(h, sent, traced, t_end, closed):
    answered = [r for r in sent if r.t_complete is not None]
    shed = sum(r.shed for r in sent)
    unanswered = sum(1 for r in sent if not r.shed and r.t_complete is None)
    lat_ms = np.array([((r.t_complete if r.t_complete is not None else t_end)
                        - r.due) * 1e3 for r in sent])
    e2e = {"p99_ms": float(np.percentile(lat_ms, 99)),
           "p50_ms": float(np.percentile(lat_ms, 50))}
    if answered:
        t_first = min(r.t_enqueue for r in sent)
        t_last = max(r.t_complete for r in answered)
        rows = sum(r.queries.shape[0] for r in answered)
        e2e["qps"] = rows / (t_last - t_first)
    if not closed:
        late = np.array([(r.t_enqueue - r.due) * 1e3 for r in sent])
        h.note(f"generator: {len(sent)} requests, late by p50 "
               f"{np.percentile(late, 50):.3f} ms, p99 "
               f"{np.percentile(late, 99):.3f} ms, max {late.max():.3f} ms")
    h.note(f"serve: {len(sent)} requests, {len(answered)} answered, {shed} "
           f"shed, p50 {e2e['p50_ms']:.3f} ms, p99 {e2e['p99_ms']:.3f} ms, "
           f"qps {e2e.get('qps', 0.0):.3f}")
    requests = [dict(rid=r.rid, rows=r.queries.shape[0], due=r.due,
                     t_enqueue=r.t_enqueue, t_admit=r.t_admit,
                     t_complete=r.t_complete, n_comps=r.n_comps,
                     n_steps=int(np.asarray(r.n_steps)),
                     traced=r.rid in traced)
                for r in answered]
    config = h.config
    groups = [Group(corpus=lambda: gen.corpus(config),
                    queries=np.concatenate([r.queries for r in answered]),
                    ids=np.concatenate([r.ids for r in answered]),
                    dists=np.concatenate([r.dists for r in answered]))]
    return Outcome(attempted=len(sent), failed=shed + unanswered,
                   unanswered=unanswered, e2e=e2e, groups=groups,
                   record={"requests": requests, "p99_ms": e2e["p99_ms"]})
