"""Beam loop (core/beam_search.py): device self time of the ops under the
program's ``beam_merge`` scope (the concat, the bounded top-k, the three
reorders of the merged candidates and the done-row keep) per beam step of
the traced requests, the base of ``beam_step_us.batch``, in microseconds."""
from bench.scopes import scope_us_per_step


def read(run):
    return scope_us_per_step(run, __file__, "beam_merge")
