"""Beam loop (core/beam_search.py): device time of the beam-search
executables in the traced segment over the beam steps they ran (the
requests' ``n_steps``), in microseconds per step."""
from bench.kernels import is_beam_search


def read(run):
    if run.trace is None:
        return None
    steps = sum(r["n_steps"] for r in run.record.get("requests", ())
                if r["traced"])
    dev = run.trace.device_time_s(is_beam_search, line="modules")
    if not steps or not dev:
        return None
    return dev / steps * 1e6
