"""Reduce a ``jax.profiler`` trace to the numbers the benchmark reports.

Only the chip's own planes are read: on each ``/device:TPU:<i>`` plane, the
``XLA Ops`` line (one event per HLO operation run on the device; a ``while``
op encloses the ops of its body) and the ``XLA Modules`` line (one event per
executable run). Names are shortened to the HLO instruction or module name
(``short_name``). The host side contributes the
harness's own ``TraceAnnotation`` spans, whose names start with ``bench.``;
the ``bench.traced`` span marks the traced window.

* busy time: the union of the operation intervals inside the window, per
  chip, averaged over the chips;
* device time of named events: the summed self time of the operations (or
  modules) whose names a metric file selects, averaged over the chips;
* idle gaps: the stretches of the window in which no operation ran on a chip,
  each labelled by the innermost ``bench.`` span that was open on the host at
  its midpoint.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.traced"


def short_name(name: str) -> str:
    """An op's HLO instruction name (``%fusion.65 = f32[...] fusion(...)``
    gives ``fusion.65``); a module's name without its program id
    (``jit_beam_search(2969...)`` gives ``jit_beam_search``)."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return name.split("(", 1)[0]


@dataclass
class Device:
    ops: list = field(default_factory=list)      # (name, start_ns, dur_ns)
    modules: list = field(default_factory=list)  # (name, start_ns, dur_ns)


@dataclass
class Trace:
    devices: list            # [Device], one per chip that ran something
    host: list               # [(name, start_ns, dur_ns)] bench.* spans

    # -- loading ------------------------------------------------------------

    @classmethod
    def from_dir(cls, path: str) -> "Trace":
        """Read the newest ``*.xplane.pb`` under a ``jax.profiler`` trace
        directory."""
        from jax.profiler import ProfileData

        files = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            raise FileNotFoundError(f"no xplane.pb under {path}")
        return cls.from_profile(ProfileData.from_file(files[-1]))

    @classmethod
    def from_profile(cls, pd) -> "Trace":
        devices, host = [], []
        for plane in pd.planes:
            if DEVICE_PLANE.match(plane.name):
                dev = Device()
                for line in plane.lines:
                    dest = {OPS_LINE: dev.ops,
                            MODULES_LINE: dev.modules}.get(line.name)
                    if dest is not None:
                        dest.extend((short_name(e.name), float(e.start_ns),
                                     float(e.duration_ns))
                                    for e in line.events)
                if dev.ops:
                    devices.append(dev)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    host.extend((e.name, float(e.start_ns),
                                 float(e.duration_ns))
                                for e in line.events
                                if e.name.startswith(HOST_PREFIX))
        return cls(devices, host)

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        return cls([Device([tuple(e) for e in d["ops"]],
                           [tuple(e) for e in d["modules"]])
                    for d in obj["devices"]],
                   [tuple(e) for e in obj["host"]])

    # -- the window ---------------------------------------------------------

    def window(self) -> tuple[float, float]:
        """(start, end) ns of the ``bench.traced`` span, else of all ops."""
        for name, s, d in self.host:
            if name == WINDOW_SPAN:
                return s, s + d
        lo = min(s for dev in self.devices for _, s, _ in dev.ops)
        hi = max(s + d for dev in self.devices for _, s, d in dev.ops)
        return lo, hi

    def window_s(self) -> float:
        lo, hi = self.window()
        return (hi - lo) * 1e-9

    def _busy_intervals(self, dev: Device) -> list:
        lo, hi = self.window()
        out = []
        for _, s, d in sorted(dev.ops, key=lambda e: e[1]):
            a, b = max(s, lo), min(s + d, hi)
            if b <= a:
                continue
            if out and a <= out[-1][1]:
                out[-1][1] = max(out[-1][1], b)
            else:
                out.append([a, b])
        return out

    def busy_s(self) -> float:
        """Seconds of the window in which an operation ran, averaged over
        the chips."""
        if not self.devices:
            return 0.0
        tot = sum(b - a for dev in self.devices
                  for a, b in self._busy_intervals(dev))
        return tot * 1e-9 / len(self.devices)

    # -- named device time --------------------------------------------------

    @staticmethod
    def _self_times(events: list) -> dict:
        """name -> summed self time (ns): an event's duration less that of
        the events nested directly inside it on the same line."""
        out = defaultdict(float)
        stack: list = []  # [name, end, nested ns, dur]

        def close():
            name, _, nested, dur = stack.pop()
            out[name] += dur - nested
            if stack:
                stack[-1][2] += dur

        for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
            while stack and stack[-1][1] <= s:
                close()
            stack.append([name, s + d, 0.0, d])
        while stack:
            close()
        return out

    def device_time_s(self, match, line: str = "ops") -> float:
        """Seconds of self time of the ops (``line="ops"``) or executables
        (``line="modules"``) whose names ``match(name)`` accepts, inside the
        window, averaged over the chips."""
        lo, hi = self.window()
        tot = 0.0
        for dev in self.devices:
            evs = [e for e in getattr(dev, line) if lo <= e[1] < hi]
            tot += sum(v for k, v in self._self_times(evs).items()
                       if match(k))
        return tot * 1e-9 / max(len(self.devices), 1)

    def top_ops(self, n: int = 10) -> list:
        """The ``n`` op names with the most self time: [[name, seconds]]."""
        lo, hi = self.window()
        tot = defaultdict(float)
        for dev in self.devices:
            evs = [e for e in dev.ops if lo <= e[1] < hi]
            for k, v in self._self_times(evs).items():
                tot[k] += v * 1e-9 / len(self.devices)
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    # -- idle gaps ----------------------------------------------------------

    def _label(self, t: float, starts: list, spans: list) -> str:
        i = bisect.bisect_right(starts, t)
        for name, s, d in reversed(spans[max(0, i - 64):i]):
            if s + d >= t and name != WINDOW_SPAN:
                return name
        return "host outside any bench span"

    def idle_gaps(self, n: int = 10) -> list:
        """Idle time of the window by what the host was doing, summed per
        label: [[label (gap count), seconds]], the ``n`` largest, averaged
        over the chips."""
        spans = sorted(self.host, key=lambda e: e[1])
        starts = [s for _, s, _ in spans]
        lo, hi = self.window()
        tot, cnt = defaultdict(float), defaultdict(int)
        for dev in self.devices:
            prev = lo
            for a, b in self._busy_intervals(dev) + [[hi, hi]]:
                if a > prev:
                    lab = self._label((prev + a) / 2, starts, spans)
                    tot[lab] += (a - prev) * 1e-9 / len(self.devices)
                    cnt[lab] += 1
                prev = max(prev, b)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[f"{k} ({cnt[k]} gaps)", v] for k, v in top]

