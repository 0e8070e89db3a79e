"""The reduction of the program's own names (``bench/scopes.py``): device
time by ``named_scope``, the ``ann.`` host spans beside the harness's
``bench.`` ones, and the per-layer metrics that read them."""
import gzip
import json
import os
import types

import numpy as np
import pytest

from bench.harness import BENCH, load_module
from bench.scopes import (SCOPES, ScopedDevice, ScopedTrace, hlo_protos,
                          hlo_scopes, scope_of)
from bench.trace import Device, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW_METRICS = ("score_us.batch", "visited_us.batch", "merge_us.batch",
               "admit_ms.open", "fetch_ms.open", "idle_server_pct.open",
               "nd_join_s.build", "nd_score_s.build", "nd_merge_s.build",
               "inferred_scope_pct.batch", "nd_round_s.build",
               "idle_build_pct.build")


def reader(name):
    return load_module(os.path.join(BENCH, "metrics", name + ".py")).read


def run_of(trace, requests=(), cell="no-such-cell"):
    return types.SimpleNamespace(trace=trace,
                                 record={"requests": list(requests)},
                                 cell={"name": cell})


def test_scope_of_takes_the_innermost_program_scope():
    path = ("jit(beam_search)/while/body/beam_score/"
            "jit(gather_distance_masked)/beam_visited/gather")
    assert scope_of(path) == "beam_visited"
    assert scope_of("jit(beam_search)/while/body/beam_merge/top_k") == \
        "beam_merge"
    assert scope_of("jit(_round_jit)/nd_merge/sort") == "nd_merge"
    # function names are not scopes
    assert scope_of("jit(beam_search)/while/cond/and") == ""
    assert scope_of("jit(beam_search_x)/beam_scores/add") == ""


@pytest.mark.parametrize("path,scope", [
    # the scorer's bit test inside the beam step is the step's bitmap work
    ("jit(beam_search)/while/body/beam_score/jit(gather_distance_masked)/"
     "visited_test/gather", "beam_visited"),
    # the seeds' test belongs to the seeding, a rerank's to the rerank
    ("jit(beam_search)/beam_init/jit(gather_distance_masked)/visited_test/"
     "and", "beam_init"),
    ("jit(beam_search)/beam_finalize/visited_test/gather", "beam_finalize"),
    # outside every phase it is no program scope
    ("jit(gather_distance_masked)/visited_test/gather", ""),
    ("jit(beam_search)/while/body/beam_score/visited_test_x/add",
     "beam_score"),
])
def test_scope_of_places_the_kernels_visited_test(path, scope):
    assert scope_of(path) == scope


# A hand-made window of 1,000 ns: a beam executable whose while op holds
# scoped ops, and host spans of both prefixes nested as the server nests
# them.
OPS = [("while.1", 0, 250, ""), ("fusion.1", 10, 50, "beam_merge"),
       ("gather_distance.3", 60, 40, "beam_score"),
       ("fusion.2", 100, 30, "beam_visited"), ("copy.1", 130, 20, ""),
       ("fusion.1", 400, 50, "beam_merge"), ("slice.1", 600, 50, "")]
HOST = [("bench.traced", 0, 1000),
        ("bench.poll", 240, 100), ("ann.poll", 245, 70),
        ("ann.retire", 250, 60), ("ann.fetch", 260, 40),
        ("bench.submit", 450, 100), ("ann.submit", 455, 90),
        ("ann.admit", 460, 80),
        ("bench.sleep", 700, 100)]
MODULES = [("jit_beam_search", 0, 460), ("jit__pad_to_bucket", 600, 50)]


def hand_made():
    dev = ScopedDevice([op[:3] for op in OPS], MODULES,
                       [op[3] for op in OPS])
    return ScopedTrace([dev], HOST)


def test_scope_times_partition_the_busy_time():
    t = hand_made()
    times = t.scope_times()
    assert times == pytest.approx({
        "beam_merge": 100e-9, "beam_score": 40e-9, "beam_visited": 30e-9,
        # the while op's own 110 ns, the copy and the slice
        "": 180e-9})
    assert sum(times.values()) == pytest.approx(t.busy_s())
    assert t.scope_time_s("beam_score") == pytest.approx(40e-9)
    assert t.scope_time_s("nd_join") == 0.0
    beam = t.scope_times(module=lambda n: n == "jit_beam_search")
    assert beam[""] == pytest.approx(130e-9)      # not the slice
    assert sum(beam.values()) == pytest.approx(300e-9)


def test_idle_gaps_take_the_innermost_span_of_either_prefix():
    t = hand_made()
    # gaps [250, 400) mid 325, [450, 600) mid 525, [650, 1000) mid 825
    assert t.idle_by_label() == pytest.approx({
        "bench.poll": 150e-9, "ann.admit": 150e-9,
        "host outside any bench span": 350e-9})
    # the harness's reduction of the same trace sees only its own spans
    plain = Trace([Device(t.devices[0].ops, MODULES)],
                  [h for h in HOST if h[0].startswith("bench.")])
    assert dict(plain.idle_gaps()) == pytest.approx({
        "bench.poll (1 gaps)": 150e-9, "bench.submit (1 gaps)": 150e-9,
        "host outside any bench span (1 gaps)": 350e-9})
    assert t.busy_s() == plain.busy_s()
    assert t.top_ops() == plain.top_ops()
    assert t.span_durations_s("ann.admit") == pytest.approx([80e-9])
    assert t.has_program_spans() and not ScopedTrace(
        t.devices, [h for h in HOST if h[0].startswith("bench.")]
    ).has_program_spans()


def test_readers_on_the_hand_made_trace():
    t = hand_made()
    reqs = [{"n_steps": 2, "traced": True}, {"n_steps": 5, "traced": False}]
    run = run_of(t, reqs)
    assert reader("score_us.batch")(run) == pytest.approx(40e-9 / 2 * 1e6)
    assert reader("merge_us.batch")(run) == pytest.approx(100e-9 / 2 * 1e6)
    assert reader("visited_us.batch")(run) == pytest.approx(30e-9 / 2 * 1e6)
    assert reader("admit_ms.open")(run) == pytest.approx(80e-9 * 1e3)
    assert reader("fetch_ms.open")(run) == pytest.approx(40e-9 * 1e3)
    # ann.admit's 150 ns of the 1,000 ns window
    assert reader("idle_server_pct.open")(run) == pytest.approx(15.0)
    # every scope there is the ops' own
    assert reader("inferred_scope_pct.batch")(run) == 0.0
    for name in ("nd_join_s.build", "nd_score_s.build", "nd_merge_s.build",
                 "nd_round_s.build"):
        assert reader(name)(run) is None


def test_inferred_scope_time_is_kept_apart():
    """Time whose scope was inferred counts under that scope, and is also
    read on its own; an op's own scope is never counted as inferred."""
    t = hand_made()
    dev = t.devices[0]
    i = [op[0] for op in OPS].index("copy.1")
    dev.scopes[i], dev.inferred[i] = "beam_visited", True
    beam = lambda n: n == "jit_beam_search"  # noqa: E731
    assert t.scope_times(beam) == pytest.approx({
        "beam_merge": 100e-9, "beam_score": 40e-9, "beam_visited": 50e-9,
        "": 110e-9})
    assert t.scope_times(beam, inferred=True) == pytest.approx(
        {"beam_visited": 20e-9})
    assert t.scope_times(beam, inferred=False) == pytest.approx({
        "beam_merge": 100e-9, "beam_score": 40e-9, "beam_visited": 30e-9,
        "": 110e-9})
    run = run_of(t, [{"n_steps": 2, "traced": True}])
    assert reader("visited_us.batch")(run) == pytest.approx(50e-9 / 2 * 1e6)
    # 20 of the beam executable's 300 ns
    assert reader("inferred_scope_pct.batch")(run) == pytest.approx(
        100 * 20 / 300)


# A hand-made build of 1,000 ns: the round's ops, host spans as
# ``GraphBuilder.build`` and NN-Descent nest them, and the probe after it.
BUILD_OPS = [("fusion.8", 50, 100, "nd_join"),
             ("fusion.9", 150, 150, "nd_merge"),
             ("fusion.8", 350, 100, "nd_join"),
             ("fusion.9", 450, 200, "nd_merge"),
             ("fusion.1", 700, 100, ""), ("fusion.2", 900, 50, "")]
BUILD_HOST = [("bench.traced", 0, 1000), ("bench.build", 0, 880),
              ("ann.construct", 30, 650), ("ann.nd_round", 40, 290),
              ("ann.nd_round", 335, 330), ("ann.diversify", 690, 120),
              ("bench.probe", 880, 120)]


def test_build_readers_on_a_hand_made_build():
    dev = ScopedDevice([op[:3] for op in BUILD_OPS],
                       [("jit__round_jit", 50, 600)],
                       [op[3] for op in BUILD_OPS])
    t = ScopedTrace([dev], BUILD_HOST)
    run = run_of(t)
    assert reader("nd_round_s.build")(run) == pytest.approx(
        (290 + 330) / 2 * 1e-9)
    # gaps [0, 50) mid 25, [300, 350) mid 325 (round 0 still open),
    # [650, 700) mid 675 (between round 1 and the diversify stage),
    # [800, 900) mid 850 (after it), [950, 1000) in the probe
    assert t.idle_by_label() == pytest.approx({
        "bench.build": 150e-9, "ann.nd_round": 50e-9,
        "ann.construct": 50e-9, "bench.probe": 50e-9})
    assert reader("idle_build_pct.build")(run) == pytest.approx(10.0)
    assert reader("idle_pct.build")(run) == pytest.approx(30.0)
    assert reader("nd_join_s.build")(run) == pytest.approx(200e-9)


def test_readers_find_nothing_without_program_names():
    """An untraced run, and a traced run of a program without scopes or
    spans (what an older program gives), read None, never raise."""
    unscoped = ScopedTrace([ScopedDevice([op[:3] for op in OPS], MODULES,
                                         [""] * len(OPS))],
                           [h for h in HOST if h[0].startswith("bench.")])
    reqs = [{"n_steps": 2, "traced": True}]
    for name in NEW_METRICS:
        assert reader(name)(run_of(None, reqs)) is None, name
        assert reader(name)(run_of(unscoped, reqs)) is None, name
        # a harness trace whose directory holds no trace of the cell
        plain = Trace([Device(unscoped.devices[0].ops, MODULES)], [])
        assert reader(name)(run_of(plain, reqs)) is None, name


@pytest.fixture(scope="module")
def recorded_build():
    with open(os.path.join(DATA, "tpu_build_trace.json")) as f:
        obj = json.load(f)
    return Trace.from_json(obj), ScopedTrace.from_json(obj)


def test_old_recorded_trace_reduces_the_same(recorded_build):
    old, new = recorded_build
    assert new.window() == old.window()
    assert new.busy_s() == old.busy_s()
    assert new.top_ops(50) == old.top_ops(50)
    assert new.idle_gaps(50) == old.idle_gaps(50)
    match = lambda n: n.startswith("gather_distance")  # noqa: E731
    assert new.device_time_s(match) == old.device_time_s(match)
    assert new.device_time_s(lambda n: n == "jit__round_jit",
                             line="modules") == \
        old.device_time_s(lambda n: n == "jit__round_jit", line="modules")
    # no program scopes in it: all of its device time is unscoped
    assert set(new.scope_times()) == {""}
    assert new.scope_times()[""] == pytest.approx(new.busy_s(), rel=1e-9)
    for name in NEW_METRICS:
        assert reader(name)(run_of(new, [{"n_steps": 3, "traced": True}])) \
            is None, name


@pytest.mark.parametrize("cell,found", [
    ("sift1m.single_open", {"admit_ms.open", "fetch_ms.open"}),
    ("sift1m.batch", set()),
])
def test_traced_cpu_run_reads_the_program_spans(cpu_harness, tiny_root, cell,
                                                found):
    """A traced run of the harness on the CPU: the readers find the trace
    where the harness wrote it. The CPU has no device plane, so only the
    host-span metrics read."""
    import time

    line, checks = cpu_harness.run_cell(tiny_root, cell, 2**31 + 9, 0.4,
                                        True, time.monotonic())
    assert line["correct"] is True, checks
    new = {m for m in line["metrics"] if m in NEW_METRICS}
    assert new == found
    for m in new:
        assert line["metrics"][m]["value"] > 0


# -- the HLO protos the profiler stores ---------------------------------------

def _varint(v):
    out = bytearray()
    while True:
        out.append((v & 0x7F) | (0x80 if v > 0x7F else 0))
        v >>= 7
        if not v:
            return bytes(out)


def _field(number, payload):
    """A length-delimited protobuf field."""
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _instr(iid, name, opcode, op_name=None, operands=(), called=()):
    """An HloInstructionProto, wrapped as its computation's field 2."""
    body = _field(1, name.encode()) + _field(2, opcode.encode())
    if op_name is not None:
        body += _field(7, _field(2, op_name.encode()))
    body += _varint(35 << 3) + _varint(iid)
    if operands:                                  # packed
        body += _field(36, b"".join(_varint(o) for o in operands))
    for c in called:                              # unpacked
        body += _varint(38 << 3) + _varint(c)
    return _field(2, body)


def _computation(name, cid, root, *instrs):
    return _field(3, _field(1, name.encode()) + b"".join(instrs)
                  + _varint(5 << 3) + _varint(cid)
                  + _varint(6 << 3) + _varint(root))


def test_hlo_scopes_of_a_hand_encoded_module():
    """Each way an instruction gets its scope: its own op_name; a fusion's
    fused root. A copy, reshape or bitcast the compiler added takes,
    inferred, the scope at the other end of its chain: operand first, else
    users that agree. Every other op without an op_name (a loop the compiler
    added and its body, a sort, the program's loop, routing ops) takes
    none, and so does a copy whose users disagree."""
    body = "jit(f)/while/body/"
    fused = _computation(
        "fused_computation.3", 1000, 301,
        _instr(300, "add.1", "add", body + "beam_score/add"),
        _instr(301, "sort.2", "sort", body + "beam_merge/sort"))
    pieces = _computation(                        # a loop the compiler added
        "wide.body.sunk", 2000, 402,
        _instr(400, "param.9", "parameter"),
        _instr(401, "dynamic-update-slice.14", "dynamic-update-slice",
               operands=(400,)),
        _instr(402, "tuple.9", "tuple", operands=(401,)))
    loop = _computation(
        "region_5", 3000, 17,
        _instr(10, "param.1", "parameter"),
        _instr(11, "get-tuple-element.3", "get-tuple-element", "",
               operands=(10,)),
        _instr(1, "fusion.65", "fusion", None, called=(1000,)),
        _instr(2, "copy.96", "copy", operands=(11,)),      # user lends
        _instr(3, "bitcast_select_fusion.7", "fusion",
               body + "beam_visited/select_n", operands=(2,)),
        _instr(4, "copy.101", "copy", operands=(3,)),      # operand lends
        _instr(5, "gather_distance.8", "custom-call",
               body + "beam_score/jit(g)/pallas_call"),
        _instr(6, "while.20", "while", "", operands=(11,),
               called=(2000,)),
        _instr(7, "get-tuple-element.4", "get-tuple-element", "",
               operands=(6,)),
        _instr(8, "fusion.64", "fusion", body + "beam_visited/gather",
               operands=(7,)),
        _instr(12, "bitcast.102", "bitcast", operands=(8,)),
        _instr(13, "copy.97", "copy", operands=(12,)),    # along a chain
        _instr(14, "reshape.279", "reshape", operands=(13,)),
        _instr(15, "copy.7", "copy", operands=(11,)),     # users disagree
        _instr(16, "add.5", "add", body + "beam_merge/add", operands=(15,)),
        _instr(18, "add.6", "add", body + "beam_select/add",
               operands=(15,)),
        _instr(19, "sort.17", "sort", operands=(3,)),     # not a chain op
        _instr(9, "reduce_and.16", "reduce", operands=(11,)),
        _instr(17, "tuple.5", "tuple", operands=(4, 8, 9, 14, 19)))
    entry = _computation(
        "main", 7, 21,
        _instr(20, "copy.5", "copy", operands=()),         # nothing near
        _instr(21, "while.6", "while", "jit(f)/while", operands=(20,),
               called=(3000,)))
    proto = _field(1, fused + pieces + loop + entry)
    scopes = hlo_scopes(proto)
    assert {k: v for k, v in scopes.items() if v[0]} == {
        "add.1": ("beam_score", False), "sort.2": ("beam_merge", False),
        "fusion.65": ("beam_merge", False),
        "copy.96": ("beam_visited", True),
        "bitcast_select_fusion.7": ("beam_visited", False),
        "copy.101": ("beam_visited", True),
        "gather_distance.8": ("beam_score", False),
        "fusion.64": ("beam_visited", False),
        "bitcast.102": ("beam_visited", True),
        "copy.97": ("beam_visited", True),
        "reshape.279": ("beam_visited", True),
        "add.5": ("beam_merge", False), "add.6": ("beam_select", False)}
    assert {k for k, v in scopes.items() if not v[0]} == {
        "param.9", "dynamic-update-slice.14", "tuple.9", "param.1",
        "get-tuple-element.3", "while.20", "get-tuple-element.4", "copy.7",
        "sort.17", "reduce_and.16", "tuple.5", "copy.5", "while.6"}
    assert all(not inf for sc, inf in scopes.values() if not sc)


def test_hlo_scopes_of_the_recorded_v5e_beam_search():
    """The compiled HLO of ``jit_beam_search`` that a v5e trace stored, at
    ``sift1m.batch``'s shapes (Q = 256). The bitmap's three relayout copies
    have no op_name and take ``beam_visited`` by inference; the merge's
    reorders, the kernel and the bit test carry their own scopes; the
    scatter's index sort and the loop stay unscoped."""
    with gzip.open(os.path.join(DATA, "tpu_beam_search_hlo.pb.gz")) as f:
        scopes = hlo_scopes(f.read())
    for name in ("copy.96", "copy.97", "copy.101"):
        assert scopes[name] == ("beam_visited", True), name
    for name, scope in (("fusion.65", "beam_merge"),
                        ("fusion.67", "beam_merge"),
                        ("gather_distance.8", "beam_score"),
                        ("compare_select_fusion.12", "beam_visited")):
        assert scopes[name] == (scope, False), name
    for name in ("sort.17", "while.2", "reduce_and.16"):
        assert scopes[name] == ("", False), name
    inferred = [k for k, (sc, inf) in scopes.items() if inf]
    assert inferred and all(k.split(".")[0] in (
        "copy", "copy-start", "copy-done", "reshape", "bitcast")
        for k in inferred)
    assert {sc for sc, _ in scopes.values()} <= {"", *SCOPES}


def test_scopes_from_a_cpu_trace(tmp_path):
    """A real trace: the profiler keeps the compiled HLO of each executable
    it saw run, and its instructions carry the program's scopes."""
    import glob

    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("beam_select"):
            y = jnp.sin(x) * 2.0
        with jax.named_scope("beam_merge"):
            return jnp.sort(y)

    x = jnp.arange(300.0)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.traced"):
        with jax.profiler.TraceAnnotation("ann.test", rid=1):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    with open(path, "rb") as fh:
        protos = hlo_protos(fh.read())
    name, = [k for k in protos if k.startswith("jit_f(")]
    scopes = {k: sc for k, (sc, _) in hlo_scopes(protos[name]).items()}
    assert {"beam_select", "beam_merge"} <= set(scopes.values())
    # the fusion of sin and multiply, and nothing from outside the scopes
    assert {v for k, v in scopes.items() if "fusion" in k} >= {"beam_select"}
    assert set(scopes.values()) <= {"", "beam_select", "beam_merge"}
    tr = ScopedTrace.from_dir(str(tmp_path))   # no device plane on the CPU
    assert tr.devices == [] and tr.span_durations_s("ann.test")


# -- a recorded v5e trace ----------------------------------------------------
# Slices of the traced runs of three cells on one TPU v5e, with the ops'
# program scopes and the host spans of both prefixes (``source`` in the
# file). Each slice has its own ``bench.traced`` span as the window.

@pytest.fixture(scope="module")
def recorded_scoped():
    with open(os.path.join(DATA, "tpu_scoped_trace.json")) as f:
        return json.load(f)


def self_by_scope_s(part, inferred=None):
    """Self time of a slice's ops by scope, counted apart from the
    reduction: each op's duration less those of the ops it holds directly
    (the shortest op holding each). ``inferred`` True or False keeps only
    the ops whose fifth field says their scope was, or was not, inferred."""
    ops = part["devices"][0]["ops"]
    held = [0.0] * len(ops)
    for j, (_, s, d) in enumerate(op[:3] for op in ops):
        holders = [i for i, (_, a, b) in enumerate(op[:3] for op in ops)
                   if i != j and a <= s and s + d <= a + b
                   and (a, -b) < (s, -d)]
        if holders:
            held[min(holders, key=lambda i: ops[i][2])] += d
    out = {}
    for op, h in zip(ops, held):
        if inferred is None or bool(op[4:5] and op[4]) == inferred:
            out[op[3]] = out.get(op[3], 0.0) + (op[2] - h) * 1e-9
    return out


def test_recorded_batch_slice(recorded_scoped):
    part = recorded_scoped["batch"]
    tr = ScopedTrace.from_json(part)
    steps = part["gather_events"]        # one gather-kernel call a step
    reqs = [{"n_steps": steps, "traced": True}]
    assert len(part["devices"][0]["ops"]) > 1000
    want_s = self_by_scope_s(part)
    for name, scope in (("score_us.batch", "beam_score"),
                        ("visited_us.batch", "beam_visited"),
                        ("merge_us.batch", "beam_merge")):
        want = want_s[scope] / steps * 1e6
        assert want > 0
        assert reader(name)(run_of(tr, reqs)) == pytest.approx(want,
                                                               rel=1e-9)
        assert reader(name)(run_of(None, reqs)) is None
    times = tr.scope_times()
    assert times == pytest.approx(want_s, rel=1e-9)
    assert sum(times.values()) == pytest.approx(tr.busy_s(), rel=1e-9)
    assert set(times) <= {"", *SCOPES}
    # the relayout copies' time, inferred, read apart
    guessed = self_by_scope_s(part, inferred=True)
    assert guessed and set(guessed) <= set(SCOPES)
    assert tr.scope_times(inferred=True) == pytest.approx(guessed, rel=1e-9)
    assert reader("inferred_scope_pct.batch")(run_of(tr, reqs)) == \
        pytest.approx(100 * sum(guessed.values()) / sum(want_s.values()),
                      rel=1e-9)


def test_recorded_build_slice(recorded_scoped):
    part = recorded_scoped["build"]
    tr = ScopedTrace.from_json(part)
    want_s = self_by_scope_s(part)
    for name, scope in (("nd_join_s.build", "nd_join"),
                        ("nd_score_s.build", "nd_score"),
                        ("nd_merge_s.build", "nd_merge")):
        want = want_s[scope]
        assert want > 0
        assert reader(name)(run_of(tr)) == pytest.approx(want, rel=1e-9)
        assert reader(name)(run_of(None)) is None


def test_recorded_open_segment(recorded_scoped):
    part = recorded_scoped["open"]
    tr = ScopedTrace.from_json(part)
    (_, lo, dur), = [h for h in part["host"] if h[0] == "bench.traced"]
    hi = lo + dur
    spans = [h for h in part["host"] if h[0] != "bench.traced"]
    for name, span in (("admit_ms.open", "ann.admit"),
                       ("fetch_ms.open", "ann.fetch")):
        durs = [d for n, s, d in spans if n == span and lo <= s < hi]
        assert durs
        assert reader(name)(run_of(tr)) == pytest.approx(
            np.mean(durs) * 1e-6, rel=1e-9)
        assert reader(name)(run_of(None)) is None
    # idle time by an independent sweep: busy intervals from sorted ends,
    # each gap labelled by the latest-starting span that covers its middle
    ops = sorted((max(s, lo), min(s + d, hi))
                 for _, s, d, _ in part["devices"][0]["ops"]
                 if min(s + d, hi) > max(s, lo))
    idle_ann, end = 0.0, lo
    for a, b in ops + [(hi, hi)]:
        if a > end:
            mid = (a + end) / 2
            cover = [(s, n) for n, s, d in spans if s <= mid <= s + d]
            if cover and max(cover)[1].startswith("ann."):
                idle_ann += a - end
        end = max(end, b)
    got = reader("idle_server_pct.open")(run_of(tr))
    assert got == pytest.approx(100 * idle_ann / dur, rel=1e-9)
    assert 0 < got < 100
    assert reader("idle_server_pct.open")(run_of(None)) is None
