"""The reduction from a profiler trace to busy time, named device time and
labelled idle gaps."""
import json
import os

import numpy as np
import pytest

from bench.trace import Device, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_hand_made_trace():
    ops = [("a", 0, 100), ("b", 10, 20), ("c", 50, 10), ("d", 200, 50)]
    host = [("bench.traced", 0, 300), ("bench.sleep", 100, 100),
            ("bench.poll", 250, 40)]
    t = Trace([Device(ops, [("jit_x", 0, 300)])], host)
    assert t.window_s() == pytest.approx(300e-9)
    assert t.busy_s() == pytest.approx(150e-9)
    assert dict(t.top_ops()) == pytest.approx(
        {"a": 70e-9, "d": 50e-9, "b": 20e-9, "c": 10e-9})
    assert t.device_time_s(lambda n: n == "jit_x", line="modules") == \
        pytest.approx(300e-9)
    assert t.idle_gaps() == [["bench.sleep (1 gaps)", pytest.approx(100e-9)],
                             ["bench.poll (1 gaps)", pytest.approx(50e-9)]]


def _union_ns(ops, lo, hi):
    """Busy ns by a running maximum of the ends, an independent sweep."""
    iv = np.array(sorted((max(s, lo), min(s + d, hi)) for _, s, d in ops
                         if min(s + d, hi) > max(s, lo)))
    prev_end = np.maximum.accumulate(np.concatenate([[lo], iv[:-1, 1]]))
    return float(np.clip(iv[:, 1] - np.maximum(iv[:, 0], prev_end), 0,
                         None).sum())


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "tpu_build_trace.json")) as f:
        return Trace.from_json(json.load(f))


def test_recorded_trace_busy_and_gaps(recorded):
    lo, hi = recorded.window()
    ops = recorded.devices[0].ops
    assert len(ops) > 1000
    busy = recorded.busy_s()
    assert busy == pytest.approx(_union_ns(ops, lo, hi) * 1e-9, rel=1e-12)
    assert 0 < busy <= recorded.window_s()
    idle = sum(s for _, s in recorded.idle_gaps(100))
    assert idle == pytest.approx(recorded.window_s() - busy, rel=1e-9)


def test_recorded_trace_self_times(recorded):
    ops = recorded.devices[0].ops
    # nested ops: self times partition the busy time
    total = sum(s for _, s in recorded.top_ops(10_000))
    assert total == pytest.approx(recorded.busy_s(), rel=1e-9)
    # the gather kernels contain no other op: their self time is their time
    gather = [d for n, _, d in ops if n.startswith("gather_distance")]
    assert len(gather) > 100
    assert recorded.device_time_s(
        lambda n: n.startswith("gather_distance")) == \
        pytest.approx(sum(gather) * 1e-9, rel=1e-12)
    modules = recorded.devices[0].modules
    assert recorded.device_time_s(lambda n: n == "jit__round_jit",
                                  line="modules") > 0
    assert any(n == "jit__round_jit" for n, _, _ in modules)
