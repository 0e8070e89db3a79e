"""The program's own spans and scopes in a ``jax.profiler`` trace.

``bench/trace.py`` reduces a trace by what the harness knows: its own
``bench.`` host spans and the anonymous HLO names of the device's ops. The
program names its work too, and :class:`ScopedTrace` reads those names on
the same clock:

* device scopes: the program runs each phase under a ``jax.named_scope``
  (``beam_*`` in ``core/beam_search.py``, ``nd_*`` in ``core/nndescent.py``),
  which XLA keeps in each instruction's ``op_name``. A device op's event
  carries no ``op_name``; the trace's ``/host:metadata`` plane holds the
  compiled HLO of each executable it ran (an ``HloProto`` per
  ``jit_name(program id)``), and an op's scope is the innermost of
  :data:`SCOPES` on the ``op_name`` of its instruction there
  (:func:`scope_of`). A copy, reshape or bitcast the compiler added
  without an ``op_name`` takes, as an inferred scope, that of the op at the
  other end of its chain (:func:`hlo_scopes`); inferred time is kept
  apart, and every other op without an ``op_name`` stays unscoped ("");
* host spans: the program's ``TraceAnnotation`` spans start with ``ann.``
  (``launch/server.py``, ``core/build.py``, ``core/nndescent.py``). They are
  kept beside the harness's ``bench.`` spans, so an idle gap is labelled by
  the innermost span of either.

On a trace with neither, every number of ``bench/trace.py`` reduces as it
does there, and the readers of program names find nothing (``None``).
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

from bench.trace import (DEVICE_PLANE, HOST_PREFIX, MODULES_LINE, OPS_LINE,
                         Device, Trace, short_name)

PROGRAM_PREFIX = "ann."
SCOPES = ("beam_init", "beam_select", "beam_expand", "beam_score",
          "beam_visited", "beam_merge", "beam_freeze", "beam_finalize",
          "nd_init", "nd_join", "nd_score", "nd_merge")
# the kernels' own scope of the visited-bitmap test (``kernels/ref.py``)
VISITED_TEST = "visited_test"
_SCOPE = re.compile(r"(?:^|/)(%s)(?=/|$)"
                    % "|".join(SCOPES + (VISITED_TEST,)))
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


def scope_of(op_name: str) -> str:
    """The innermost program scope on an HLO ``op_name`` path, else "". The
    kernels' ``visited_test`` counts as ``beam_visited`` directly inside the
    beam step's ``beam_score``, and as the scope around it anywhere else
    (the seeds' test is ``beam_init``)."""
    found = _SCOPE.findall(op_name)
    if found and found[-1] == VISITED_TEST:
        found.pop()
        if found and found[-1] == "beam_score":
            return "beam_visited"
    return found[-1] if found else ""


# -- the HLO the profiler stores, read from its protobuf wire format ----------

def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one protobuf message: an int
    for a varint, a memoryview of the bytes otherwise."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield field, value


def _ints(value) -> list:
    """A repeated int64 field's value: packed bytes or one varint."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def hlo_protos(xspace: bytes) -> dict:
    """``jit_name(program id)`` -> serialized ``HloProto`` of each executable
    in an ``XSpace`` (the ``.xplane.pb`` file): the ``Hlo Proto`` stats of
    the ``/host:metadata`` plane's event metadata."""
    for field, plane in _fields(memoryview(xspace)):
        if field != 1:                                   # XSpace.planes
            continue
        fields = list(_fields(plane))
        if bytes(next((v for f, v in fields if f == 2), b"")) != \
                METADATA_PLANE.encode():
            continue
        stat_ids = set()
        for f, entry in fields:                          # stat_metadata
            if f == 5:
                meta = dict(_fields(dict(_fields(entry))[2]))
                if bytes(meta.get(2, b"")) == HLO_PROTO_STAT.encode():
                    stat_ids.add(meta.get(1))
        out = {}
        for f, entry in fields:                          # event_metadata
            if f != 4:
                continue
            meta = dict(_fields(entry)).get(2, b"")
            name, proto = "", None
            for f2, v in _fields(meta):
                if f2 == 2:
                    name = bytes(v).decode()
                elif f2 == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) in stat_ids and 6 in stat:
                        proto = bytes(stat[6])
            if proto is not None:
                out[name] = proto
        return out
    return {}


# ops that only move or relabel one array: a relayout copy (or its
# asynchronous start and done), a reshape, a bitcast
_CHAIN = frozenset(("copy", "copy-start", "copy-done", "reshape", "bitcast"))


def hlo_scopes(hlo_proto: bytes) -> dict:
    """Instruction name -> (program scope or "", inferred) of one
    executable's serialized ``HloProto``. An instruction's scope is the one
    its own ``op_name`` names; a fusion's is its fused computation's (the
    root's, else the one most of its instructions carry). A :data:`_CHAIN`
    op the compiler added without a scope takes, inferred, the scope of the
    op at the other end of its chain of such ops: its operand's, else its
    users' where they all name one. Every other op without a scope (the
    program's loop control, a scatter's index sort, a loop the compiler
    added) stays unscoped."""
    ins = {}       # id -> (name, opcode, own scope, operands, called)
    comps = {}     # computation id -> (root id, [instruction ids])
    for f, module in _fields(memoryview(hlo_proto)):
        if f != 1:                                       # HloProto.hlo_module
            continue
        for f2, comp in _fields(module):
            if f2 != 3:                                  # .computations
                continue
            cid = root = 0
            members = []
            for f3, v in _fields(comp):
                if f3 == 5:
                    cid = v
                elif f3 == 6:
                    root = v
                elif f3 == 2:                            # .instructions
                    iid, name, opcode, op_name = 0, "", "", ""
                    operands, called = [], []
                    for f4, w in _fields(v):
                        if f4 == 1:
                            name = bytes(w).decode()
                        elif f4 == 2:
                            opcode = bytes(w).decode()
                        elif f4 == 7:                    # OpMetadata.op_name
                            op_name = bytes(dict(_fields(w)).get(2, b"")
                                            ).decode()
                        elif f4 == 35:
                            iid = w
                        elif f4 == 36:
                            operands += _ints(w)
                        elif f4 == 38:
                            called += _ints(w)
                    ins[iid] = (name, opcode, scope_of(op_name), operands,
                                called)
                    members.append(iid)
            comps[cid] = (root, members)
    users = defaultdict(list)
    for iid, (_, _, _, operands, _) in ins.items():
        for o in operands:
            users[o].append(iid)

    def own(iid):
        _, opcode, scope, _, called = ins[iid]
        if scope or opcode != "fusion" or not called:
            return scope
        root, members = comps.get(called[0], (0, []))
        if root in ins and ins[root][2]:
            return ins[root][2]
        found = [ins[i][2] for i in members if ins[i][2]]
        return max(set(found), key=found.count) if found else ""

    def along_chain(iid):
        j = iid
        while len(ins[j][3]) == 1 and ins[j][3][0] in ins:
            j = ins[j][3][0]
            if own(j):
                return own(j)
            if ins[j][1] not in _CHAIN:
                break
        frontier, seen = [iid], {iid}
        while frontier:
            nxt = [u for i in frontier for u in users[i] if u not in seen]
            seen.update(nxt)
            found = {own(u) for u in nxt if own(u)}
            if found:
                return found.pop() if len(found) == 1 else ""
            frontier = [u for u in nxt if ins[u][1] in _CHAIN]
        return ""

    out = {}
    for iid, (name, opcode, _, _, _) in ins.items():
        scope = own(iid)
        if scope:
            out[name] = (scope, False)
        else:
            scope = along_chain(iid) if opcode in _CHAIN else ""
            out[name] = (scope, bool(scope))
    return out


@dataclass
class ScopedDevice(Device):
    scopes: list = field(default_factory=list)   # one per op, "" unscoped
    inferred: list = field(default_factory=list)  # one per op: scope guessed

    def __post_init__(self):
        self.inferred += [False] * (len(self.ops) - len(self.inferred))


@dataclass
class ScopedTrace(Trace):
    """A :class:`Trace` whose ops carry their program scope and whose host
    spans include the program's ``ann.`` spans."""

    # -- loading ------------------------------------------------------------

    @classmethod
    def from_dir(cls, path: str) -> "ScopedTrace":
        from jax.profiler import ProfileData

        with open(_newest_xplane(path), "rb") as f:
            data = f.read()
        return cls.from_profile(ProfileData.from_serialized_xspace(data),
                                hlo_protos(data))

    @classmethod
    def from_profile(cls, pd, protos: dict) -> "ScopedTrace":
        """``protos``: :func:`hlo_protos` of the same trace."""
        scopes = {name: hlo_scopes(proto) for name, proto in protos.items()}
        devices, host = [], []
        for plane in pd.planes:
            if DEVICE_PLANE.match(plane.name):
                lines = {line.name: line for line in plane.lines}
                dev = ScopedDevice()
                if MODULES_LINE in lines:
                    dev.modules.extend(
                        (short_name(e.name), float(e.start_ns),
                         float(e.duration_ns))
                        for e in lines[MODULES_LINE].events)
                if OPS_LINE in lines:
                    _read_ops(lines[OPS_LINE], lines.get(MODULES_LINE), dev,
                              scopes)
                if dev.ops:
                    devices.append(dev)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    host.extend((e.name, float(e.start_ns),
                                 float(e.duration_ns))
                                for e in line.events
                                if e.name.startswith((HOST_PREFIX,
                                                      PROGRAM_PREFIX)))
        return cls(devices, host)

    @classmethod
    def from_json(cls, obj: dict) -> "ScopedTrace":
        """Ops as [name, start, dur], [name, start, dur, scope] or [name,
        start, dur, scope, inferred]."""
        devices = []
        for d in obj["devices"]:
            ops = d["ops"]
            devices.append(ScopedDevice(
                [tuple(e[:3]) for e in ops], [tuple(e) for e in d["modules"]],
                [e[3] if len(e) > 3 else "" for e in ops],
                [bool(e[4]) if len(e) > 4 else False for e in ops]))
        return cls(devices, [tuple(e) for e in obj["host"]])

    # -- device time by scope -----------------------------------------------

    def scope_times(self, module=None, inferred=None) -> dict:
        """scope -> seconds of self time of the ops under it ("" for ops
        under none) inside the window, averaged over the chips. ``module``
        (a predicate on module names) keeps only the ops that ran inside a
        matching executable; ``inferred`` True keeps only the ops whose
        scope :func:`hlo_scopes` inferred, False only the others."""
        lo, hi = self.window()
        tot = defaultdict(float)
        for dev in self.devices:
            inside = _inside(dev, module)
            evs = [((sc, inf), s, d) for (_, s, d), sc, inf, keep
                   in zip(dev.ops, dev.scopes, dev.inferred, inside)
                   if keep and lo <= s < hi]
            for (sc, inf), v in self._self_times(evs).items():
                if inferred is None or inf == inferred:
                    tot[sc] += v * 1e-9 / len(self.devices)
        return dict(tot)

    def scope_time_s(self, scope: str) -> float:
        """Seconds of self time of the ops whose innermost program scope is
        ``scope``, inside the window, averaged over the chips."""
        return self.scope_times().get(scope, 0.0)

    # -- host spans ---------------------------------------------------------

    def span_durations_s(self, name: str) -> list:
        """Durations (s) of the host spans named ``name`` that start inside
        the window."""
        lo, hi = self.window()
        return [d * 1e-9 for n, s, d in self.host
                if n == name and lo <= s < hi]

    def has_program_spans(self) -> bool:
        return any(n.startswith(PROGRAM_PREFIX) for n, _, _ in self.host)

    def idle_by_label(self) -> dict:
        """Idle seconds of the window by the innermost host span open at
        each gap's midpoint (``bench.`` or ``ann.``), averaged over the
        chips: the sums ``idle_gaps`` ranks, for every label."""
        spans = sorted(self.host, key=lambda e: e[1])
        starts = [s for _, s, _ in spans]
        lo, hi = self.window()
        tot = defaultdict(float)
        for dev in self.devices:
            prev = lo
            for a, b in self._busy_intervals(dev) + [[hi, hi]]:
                if a > prev:
                    lab = self._label((prev + a) / 2, starts, spans)
                    tot[lab] += (a - prev) * 1e-9 / len(self.devices)
                prev = max(prev, b)
        return dict(tot)


def _newest_xplane(path: str) -> str:
    files = sorted(glob.glob(os.path.join(
        path, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no xplane.pb under {path}")
    return files[-1]


def _read_ops(ops_line, modules_line, dev: ScopedDevice,
              scopes: dict) -> None:
    """Append the line's ops and their scopes to ``dev``: an op's executable
    is the ``XLA Modules`` event (``jit_name(program id)``) that holds it,
    and ``scopes`` maps each executable to its instructions' scopes."""
    mods = sorted(((float(e.start_ns), float(e.start_ns + e.duration_ns),
                    e.name) for e in modules_line.events)
                  if modules_line is not None else [])
    mod_starts = [m[0] for m in mods]
    for e in ops_line.events:
        s = float(e.start_ns)
        i = bisect.bisect_right(mod_starts, s) - 1
        mod = mods[i][2] if i >= 0 and s < mods[i][1] else ""
        name = short_name(e.name)
        scope, inferred = scopes.get(mod, {}).get(name, ("", False))
        dev.ops.append((name, s, float(e.duration_ns)))
        dev.scopes.append(scope)
        dev.inferred.append(inferred)


def _inside(dev: Device, module) -> list:
    """Per op of ``dev``: whether it ran inside an executable whose name
    ``module`` accepts (every op where ``module`` is None)."""
    if module is None:
        return [True] * len(dev.ops)
    spans = sorted((s, s + d) for n, s, d in dev.modules if module(n))
    starts = [s for s, _ in spans]
    out = []
    for _, s, _ in dev.ops:
        i = bisect.bisect_right(starts, s) - 1
        out.append(i >= 0 and s < spans[i][1])
    return out


# -- what the per-layer readers share ---------------------------------------

@functools.lru_cache(maxsize=1)
def _load_dir(path: str, mtime_ns: int) -> ScopedTrace:
    return ScopedTrace.from_dir(path)


def scoped(run, reader_file: str) -> ScopedTrace | None:
    """The traced run's trace with the program's names, or None for an
    untraced run. ``reader_file`` is the metric file's own path: the trace
    lies where that checkout's harness wrote it, ``bench/.cache/trace/
    <cell>``. A run whose ``trace`` is already a :class:`ScopedTrace` (a
    recorded one) is read as it is."""
    if run.trace is None:
        return None
    if isinstance(run.trace, ScopedTrace):
        return run.trace
    bench = os.path.dirname(os.path.dirname(os.path.abspath(reader_file)))
    path = os.path.join(bench, ".cache", "trace", run.cell["name"])
    try:
        newest = _newest_xplane(path)
    except FileNotFoundError:
        return None
    return _load_dir(path, os.stat(newest).st_mtime_ns)


def traced_steps(run) -> int:
    """Beam steps of the traced requests (``n_steps``), the base of
    ``beam_step_us.batch``."""
    return sum(r["n_steps"] for r in run.record.get("requests", ())
               if r["traced"])


def scope_us_per_step(run, reader_file: str, scope: str) -> float | None:
    """Device self time under ``scope`` per traced beam step, in us."""
    tr = scoped(run, reader_file)
    steps = traced_steps(run)
    if tr is None or not steps:
        return None
    t = tr.scope_time_s(scope)
    return t / steps * 1e6 if t else None


def scope_s(run, reader_file: str, scope: str) -> float | None:
    """Device self time under ``scope`` in the traced segment, in s."""
    tr = scoped(run, reader_file)
    if tr is None:
        return None
    return tr.scope_time_s(scope) or None


def inferred_pct(run, reader_file: str, module) -> float | None:
    """Share of the device self time of the executables ``module`` accepts
    whose scope was inferred, not named by the op's own ``op_name``, in
    percent; None where no op there carries a scope."""
    tr = scoped(run, reader_file)
    if tr is None:
        return None
    times = tr.scope_times(module)
    total = sum(times.values())
    if not total or not total - times.get("", 0.0):
        return None
    return 100.0 * sum(tr.scope_times(module, inferred=True).values()) / total


def span_mean(run, reader_file: str, name: str,
              scale: float = 1.0) -> float | None:
    """Mean duration of the program's host span ``name``, in s times
    ``scale``."""
    tr = scoped(run, reader_file)
    durs = tr.span_durations_s(name) if tr is not None else []
    return scale * sum(durs) / len(durs) if durs else None


def program_idle_pct(run, reader_file: str) -> float | None:
    """Share of the traced window in which no op ran on the chip while one
    of the program's ``ann.`` spans was the innermost open on the host, in
    percent; None where the trace holds no such span."""
    tr = scoped(run, reader_file)
    if tr is None or not tr.devices or not tr.has_program_spans():
        return None
    idle = sum(v for k, v in tr.idle_by_label().items()
               if k.startswith(PROGRAM_PREFIX))
    return 100.0 * idle / tr.window_s()
