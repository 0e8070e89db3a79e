"""Beam loop (core/beam_search.py): device self time of the ops under the
program's ``beam_visited`` scope (the bit test of the visited bitmap, its
marking and the done-row keep) per beam step of the traced requests, the
base of ``beam_step_us.batch``, in microseconds."""
from bench.scopes import scope_us_per_step


def read(run):
    return scope_us_per_step(run, __file__, "beam_visited")
