"""Continuous-batching ANN query server (DESIGN.md §11).

``launch/serve.py``'s original ann loop pushed pre-formed, equal-sized query
batches through one compiled beam core — a throughput harness. Production
traffic is ragged (requests carry 1..B queries), bursty (Poisson arrivals,
not a closed loop) and latency-bound (p99 is the SLO, not batch wall). This
module is the serving layer between the two:

    submit() ──> request queue ──> bucket pad ──> admission ──> beam core
       │            (shed past       (smallest      (<= max_live    (one
       │          max_queue_depth)   bucket that     batches in    compiled
       │                             fits, q_valid   flight)       core per
       └── timestamps: enqueue ─ admit ─ dispatch ─ complete ──────bucket)

* **Buckets.** Each request is padded up to the smallest configured bucket
  that fits; one beam core is compiled per ``(bucket_Q, SearchSpec)`` and
  cached by jit (``warmup()`` compiles all of them off the serving path).
  Padding rows ride the engine's ``q_valid`` mask: zero comparisons, no
  effect on real rows, so a served request is BIT-IDENTICAL to a direct
  ``Searcher.search`` on its own rows (locked by tests/test_server.py).
  Seeding runs on the request's real rows BEFORE padding — that is what
  keeps key-dependent strategies (``random``) parity-exact, since a PRNG
  draw at the bucket shape would not match the request-shaped draw.
  Adaptive termination (``spec.term="stable"``) and restarts ride the same
  contract: frozen rows reuse the pad-row masking (zero further comps) and
  restart keys are a function of the ROW INDEX, so bucketed results stay
  bit-identical to direct searches under per-query early exit too.
* **Admission control.** At most ``max_live_batches`` dispatched-and-
  unretired batches; beyond that requests wait in the queue, and past
  ``max_queue_depth`` new requests are shed at submit time (recorded, never
  silently dropped) — queueing delay is bounded by design instead of
  growing without limit under overload.
* **Overlap.** ``_admit`` issues the request's host->device input copy
  (``jax.device_put``) and the jitted search dispatch asynchronously:
  while batch i is still executing, batch i+1's rows are already in
  flight and its seeding/LUT build runs on the host — the §9 tile-prefetch
  pipeline generalized from stream tiles to independent requests.
  ``poll()`` retires finished batches without blocking (``is_ready``), so
  completion timestamps track device completion, not caller convenience.
* **Accounting.** Every request carries enqueue/admit/dispatch/complete
  timestamps and its beam step count; ``stats()`` rolls them into
  p50/p90/p99 latency, queue wait, bucket occupancy and shed counts — the
  columns ``benchmarks/loadgen.py`` sweeps against offered QPS into
  ``BENCH_engine.json``.
* **Spans.** Under ``jax.profiler`` the host work is a tree of
  ``TraceAnnotation`` spans on the device trace's clock, each tagged with
  the request's ``rid`` and ``bucket``: ``ann.submit`` and ``ann.poll``
  hold ``ann.admit`` (children ``ann.seed``: transfer and seeding,
  ``ann.pad``, ``ann.dispatch``: the beam core call) and ``ann.retire``
  (children ``ann.wait``: the device wait, ``ann.fetch``: the copies to the
  host). Untraced, a span costs about a microsecond.
* **Hot swap.** ``swap()`` (DESIGN.md §13) atomically flips serving to a
  new index version: the incoming index is prepared and fully warmed OFF
  the serving path, then the flip is two attribute assignments — zero
  dropped requests, zero post-flip compiles.
* **Per-request filters.** ``submit(..., filter=FilterSpec(...))``
  (DESIGN.md §14) restricts that request to a metadata predicate / tenant
  namespace. The filter's deny bitmap is a beam-core OPERAND (compiled
  once per filter value, cached on the Searcher), so mixed-filter traffic
  shares the bucket executables, and a served filtered request stays
  bit-identical to a direct filtered search on its own rows.
"""
from __future__ import annotations

import bisect
import functools
import time
from collections import deque
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.beam_search import SearchResult
from repro.core.engine import Searcher, SearchSpec
from repro.core.filters import FilterSpec
from repro.core.topk import INVALID


class ServeConfig(NamedTuple):
    """Static serving-layer configuration (the knobs around one SearchSpec)."""

    buckets: tuple[int, ...] = (1, 2, 4, 8, 16, 32)
    max_live_batches: int = 4   # admission cap: dispatched, not yet retired
    max_queue_depth: int = 64   # shed submits beyond this backlog


@dataclass
class Request:
    """One client request: a (q, d) block of host-resident query rows plus
    its full latency trail. ``shed`` requests never reach the device."""

    rid: int
    queries: np.ndarray
    key: jax.Array
    t_enqueue: float
    t_admit: float | None = None
    t_dispatch: float | None = None
    t_complete: float | None = None
    bucket: int | None = None
    shed: bool = False
    # per-request predicate (§14): rides into the spec as an operand swap,
    # so mixed-filter batches reuse the bucket's compiled cores
    filter: FilterSpec | None = None
    ids: np.ndarray | None = None       # (q, k) answers, real rows only
    dists: np.ndarray | None = None     # (q, k)
    n_comps: np.ndarray | None = None   # (q,)
    n_steps: jax.Array | None = None    # () beam steps, left on the device

    @property
    def latency_s(self) -> float:
        return self.t_complete - self.t_enqueue

    @property
    def queue_wait_s(self) -> float:
        return self.t_admit - self.t_enqueue


class _LiveBatch(NamedTuple):
    request: Request
    result: SearchResult


def _percentiles(ms: np.ndarray) -> dict:
    return {
        "p50_ms": round(float(np.percentile(ms, 50)), 3),
        "p90_ms": round(float(np.percentile(ms, 90)), 3),
        "p99_ms": round(float(np.percentile(ms, 99)), 3),
        "mean_ms": round(float(ms.mean()), 3),
    }


def _span(name: str, **tags):
    """A host span of the serving path in a ``jax.profiler`` trace."""
    return jax.profiler.TraceAnnotation("ann." + name, **tags)


@functools.partial(jax.jit, static_argnames=("bucket",))
def _pad_to_bucket(queries, entries, entry_comps, bucket: int):
    """Pad a request's (queries, entries, comps) up to ``bucket`` rows —
    zero queries, INVALID entries, zero comps — plus the (bucket,) q_valid
    mask of its real rows."""
    pad = bucket - queries.shape[0]
    return (
        jnp.pad(queries, ((0, pad), (0, 0))),
        jnp.pad(entries, ((0, pad), (0, 0)), constant_values=INVALID),
        jnp.pad(entry_comps, (0, pad)),
        jnp.arange(bucket) < queries.shape[0],
    )


class AnnServer:
    """Continuous-batching front end over one :class:`Searcher` + spec.

    Single-threaded by design: JAX dispatch is asynchronous, so one host
    thread can keep ``max_live_batches`` batches in flight — admission,
    transfer and seeding of request i+1 happen while request i executes on
    the device. Drive it with ``submit``/``poll`` (open loop, shedding) or
    ``submit_wait``/``drain`` (closed loop, backpressure)."""

    def __init__(self, searcher: Searcher, spec: SearchSpec,
                 config: ServeConfig = ServeConfig(),
                 clock=time.monotonic):
        if not config.buckets or list(config.buckets) != sorted(
                set(config.buckets)) or config.buckets[0] < 1:
            raise ValueError(
                f"buckets must be sorted unique positive sizes, got "
                f"{config.buckets!r}"
            )
        if config.max_live_batches < 1 or config.max_queue_depth < 1:
            raise ValueError("max_live_batches and max_queue_depth must be "
                             ">= 1")
        self.searcher = searcher
        self.spec = spec
        self.config = config
        self.clock = clock
        self.queue: deque[Request] = deque()
        self.live: deque[_LiveBatch] = deque()
        self.completed: list[Request] = []
        self.shed: list[Request] = []
        self._rid = 0
        self.bucket_counts = {b: 0 for b in config.buckets}
        self.real_rows = 0
        self.padded_rows = 0
        # hot-swap bookkeeping (DESIGN.md §13): the serving index version,
        # bumped by every atomic flip, plus a flip event log
        self.version = 0
        self.swap_events: list[dict] = []
        # per-index state built once, off the serving path: strategy aux,
        # PQ code table, host base mirror
        self._prepare_index(searcher, spec)

    @staticmethod
    def _prepare_index(searcher: Searcher, spec: SearchSpec) -> None:
        searcher.prepare(spec)
        if spec.scorer == "pq":
            searcher.pq_index(spec)
        elif spec.scorer == "sq8":
            searcher.sq8_index()
        if spec.base_placement != "device":
            searcher.base_store(spec.base_placement, spec.store_dtype)

    # -- bucketing ------------------------------------------------------------

    def pick_bucket(self, q: int) -> int:
        """Smallest configured bucket that fits a q-row request."""
        if q < 1:
            raise ValueError(f"request must carry >= 1 query row, got {q}")
        i = bisect.bisect_left(self.config.buckets, q)
        if i == len(self.config.buckets):
            raise ValueError(
                f"request of {q} rows exceeds the largest bucket "
                f"{self.config.buckets[-1]}; split it client-side or widen "
                f"ServeConfig.buckets"
            )
        return self.config.buckets[i]

    def warmup(self, key: jax.Array | None = None, *,
               searcher: Searcher | None = None,
               spec: SearchSpec | None = None) -> None:
        """Compile every shape the serving path can hit, off the serving
        path. One beam core per (bucket, spec) is not enough: seeding runs
        at the request's REAL row count and the pad ops are shape-keyed
        too, so each distinct qn is its own set of executables — the first
        size-3 request would otherwise pay a trace+compile spike mid-
        serving. qn only ranges 1..max_bucket, so warming each qn once
        covers every (qn, bucket) pair the server can ever see.

        ``searcher``/``spec`` (default: the serving pair) let :meth:`swap`
        warm an INCOMING index before the flip — its (n, W) shapes key new
        executables whenever n changed, and tracing them on the serving path
        would spike p99 mid-flip.

        When the index carries metadata columns (or the spec itself
        filters), each bucket is ALSO warmed with a deny bitmap attached:
        the deny-operand beam executables differ from the unfiltered ones
        (an extra operand), but are shared across every filter VALUE — one
        structural warmup per bucket covers all tenants/predicates (§14).
        The warm filter is a 1-id denylist, so it needs no metadata and
        always takes the graph path on any real-sized index."""
        searcher = self.searcher if searcher is None else searcher
        spec = self.spec if spec is None else spec
        d = searcher.base.shape[1]
        key = searcher.key if key is None else key
        b_max = self.config.buckets[-1]
        rows = np.asarray(
            jax.random.normal(jax.random.fold_in(key, b_max), (b_max, d)),
            np.float32,
        )
        warm_filter = (searcher.metadata is not None
                       or spec.filter is not None)
        for qn in range(1, b_max + 1):
            res = self._search_padded(rows[:qn],
                                      jax.random.fold_in(key, 2 * qn),
                                      self.pick_bucket(qn),
                                      searcher=searcher, spec=spec)
            jax.block_until_ready(res.ids)
            if warm_filter:
                res = self._search_padded(rows[:qn],
                                          jax.random.fold_in(key, 2 * qn),
                                          self.pick_bucket(qn),
                                          searcher=searcher, spec=spec,
                                          filter=FilterSpec(deny_ids=(0,)))
                jax.block_until_ready(res.ids)

    # -- the padded core call -------------------------------------------------

    def _search_padded(self, rows: np.ndarray, key: jax.Array,
                       bucket: int, *, searcher: Searcher | None = None,
                       spec: SearchSpec | None = None,
                       filter: FilterSpec | None = None,
                       rid: int = -1) -> SearchResult:
        """Transfer + seed + pad + dispatch, all asynchronous. Seeding uses
        the request's REAL row count (PRNG parity with a direct search);
        padding to the bucket happens after, with entries INVALID, comps 0
        and ``q_valid`` masking the pad rows out of the beam. ``searcher``/
        ``spec`` target an index other than the serving one (warming an
        incoming index pre-flip). ``filter`` overrides ``spec.filter`` for
        this request (§14): denied-seed redraws key off the ROW INDEX, so
        the padded rows redraw exactly as a direct filtered search would.
        ``rid`` tags the spans (-1 off the request path)."""
        searcher = self.searcher if searcher is None else searcher
        spec = self.spec if spec is None else spec
        if filter is not None:
            spec = spec._replace(filter=filter)
        with _span("seed", rid=rid, bucket=bucket):
            dev = jax.device_put(rows)  # async: overlaps the in-flight batch
            ent, ecomps = searcher.seed(dev, spec, key)
        with _span("pad", rid=rid, bucket=bucket):
            dev, ent, ecomps, valid = _pad_to_bucket(dev, ent, ecomps,
                                                     bucket)
        # the request key ALSO rides into the search: restart keys are
        # fold_in(key, row_index), so the real rows of a padded bucket draw
        # the exact restart seeds a direct search would (pad rows hold keys
        # too but can never restart — they finish with an empty beam)
        with _span("dispatch", rid=rid, bucket=bucket):
            return searcher.search(dev, spec, key, entries=ent,
                                   entry_comps=ecomps, q_valid=valid)

    # -- hot swap (DESIGN.md §13) ---------------------------------------------

    def swap(self, searcher: Searcher, spec: SearchSpec | None = None,
             key: jax.Array | None = None) -> int:
        """Atomically flip serving to a new index version with zero dropped
        requests and no on-path compilation.

        The incoming index is fully prepared OFF the serving path first:
        strategy aux / PQ table / host mirror, then a full :meth:`warmup` —
        every (qn, bucket) executable for the incoming (n, W) shapes is
        compiled and cached BEFORE the flip. The flip itself is two
        attribute assignments: in-flight batches keep device references to
        the old arrays (retire never touches ``self.searcher``), requests
        admitted afterwards run on the new version, nothing is shed or
        retraced mid-flip. Returns the new version number."""
        spec = self.spec if spec is None else spec
        self._prepare_index(searcher, spec)
        t0 = self.clock()
        self.warmup(key, searcher=searcher, spec=spec)   # pre-flip: off-path
        warmed = self.clock()
        # the atomic flip — everything after this line serves v+1
        self.searcher = searcher
        self.spec = spec
        self.version += 1
        self.swap_events.append({
            "version": self.version,
            "n": int(searcher.base.shape[0]),
            "warm_s": round(warmed - t0, 4),
            "t_flip": self.clock(),
            "live_at_flip": len(self.live),
            "queued_at_flip": len(self.queue),
        })
        return self.version

    # -- request lifecycle ----------------------------------------------------

    def submit(self, rows, key: jax.Array | None = None,
               now: float | None = None, advance: bool = True,
               filter: FilterSpec | None = None) -> Request:
        """Enqueue one request (open loop). Returns the Request handle; if
        the queue is at ``max_queue_depth`` the request is SHED — marked and
        recorded, never dispatched — so overload degrades by rejecting new
        work instead of growing unbounded queueing delay.

        ``advance=False`` enqueues without driving :meth:`poll` — how an
        open-loop client behind schedule behaves: the listener half accepts
        (or sheds) without stealing serving-thread time from the batches in
        flight. ``filter`` (optional) restricts THIS request to a metadata
        predicate / tenant namespace (§14)."""
        now = self.clock() if now is None else now
        rows = np.asarray(rows, np.float32)
        if rows.ndim != 2:
            raise ValueError(f"rows must be (q, d), got shape {rows.shape}")
        rid = self._rid
        self._rid += 1
        with _span("submit", rid=rid):
            if key is None:
                key = jax.random.fold_in(self.searcher.key, 1_000_003 + rid)
            req = Request(rid=rid, queries=rows, key=key, t_enqueue=now,
                          filter=filter)
            req.bucket = self.pick_bucket(rows.shape[0])  # reject-too-big
            if len(self.queue) >= self.config.max_queue_depth:
                req.shed = True
                self.shed.append(req)
                return req
            self.queue.append(req)
            if advance:
                self.poll(now)
        return req

    def submit_wait(self, rows, key: jax.Array | None = None,
                    filter: FilterSpec | None = None) -> Request:
        """Closed-loop submit: when the queue is full, block on the oldest
        in-flight batch instead of shedding (backpressure for clients that
        wait, e.g. the CI serving smoke)."""
        while len(self.queue) >= self.config.max_queue_depth:
            if self.live:
                self._retire(self.live.popleft())
            self.poll()
        return self.submit(rows, key, filter=filter)

    def poll(self, now: float | None = None) -> None:
        """Advance the pipeline without blocking: retire finished batches
        from the head of the live window (dispatch order == completion
        order on one device stream), then admit queued requests up to the
        admission cap."""
        with _span("poll"):
            while self.live and self._ready(self.live[0]):
                self._retire(self.live.popleft())
            cap = self.config.max_live_batches
            while self.queue and len(self.live) < cap:
                self._admit(self.queue.popleft())

    def drain(self) -> list[Request]:
        """Block until every queued and in-flight request completes."""
        while self.live or self.queue:
            if self.live:
                self._retire(self.live.popleft())
            self.poll()
        return self.completed

    def _ready(self, lb: _LiveBatch) -> bool:
        is_ready = getattr(lb.result.ids, "is_ready", None)
        return True if is_ready is None else bool(is_ready())

    def _admit(self, req: Request) -> None:
        with _span("admit", rid=req.rid, bucket=req.bucket):
            req.t_admit = self.clock()
            res = self._search_padded(req.queries, req.key, req.bucket,
                                      filter=req.filter, rid=req.rid)
            req.t_dispatch = self.clock()
        qn = req.queries.shape[0]
        self.bucket_counts[req.bucket] += 1
        self.real_rows += qn
        self.padded_rows += req.bucket - qn
        self.live.append(_LiveBatch(req, res))

    def _retire(self, lb: _LiveBatch) -> None:
        res, req = lb.result, lb.request
        tags = dict(rid=req.rid, bucket=req.bucket)
        with _span("retire", **tags):
            with _span("wait", **tags):
                jax.block_until_ready(res.ids)
            req.t_complete = self.clock()
            qn = req.queries.shape[0]
            with _span("fetch", **tags):
                req.ids = np.asarray(res.ids)[:qn]
                req.dists = np.asarray(res.dists)[:qn]
                req.n_comps = np.asarray(res.n_comps)[:qn]
            req.n_steps = res.n_steps
            self.completed.append(req)

    # -- rollups --------------------------------------------------------------

    def stats(self) -> dict:
        """Latency profile + occupancy over everything completed so far."""
        out = {
            "completed": len(self.completed),
            "shed": len(self.shed),
            "version": self.version,
            "swaps": len(self.swap_events),
            "bucket_counts": {str(b): c for b, c in
                              self.bucket_counts.items() if c},
            "real_rows": self.real_rows,
            "padded_rows": self.padded_rows,
            "mean_fill": round(
                self.real_rows / max(self.real_rows + self.padded_rows, 1), 4
            ),
        }
        if self.completed:
            lat = np.array([r.latency_s for r in self.completed]) * 1e3
            out.update(_percentiles(lat))
            waits = np.array([r.queue_wait_s for r in self.completed]) * 1e3
            out["mean_queue_ms"] = round(float(waits.mean()), 3)
            span = (max(r.t_complete for r in self.completed)
                    - min(r.t_enqueue for r in self.completed))
            out["sustained_qps"] = round(self.real_rows / max(span, 1e-9), 1)
        return out
