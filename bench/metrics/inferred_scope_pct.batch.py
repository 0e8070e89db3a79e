"""Beam loop (core/beam_search.py): the share of the beam-search executables'
device self time in the traced segment whose program scope was inferred (a
copy, reshape or bitcast the compiler added without an ``op_name``, given the
scope at the other end of its chain) rather than named by the op itself, in
percent. ``score_us.batch``, ``visited_us.batch`` and ``merge_us.batch``
count that time; a change here moves them without a change of work."""
from bench.kernels import is_beam_search
from bench.scopes import inferred_pct


def read(run):
    return inferred_pct(run, __file__, is_beam_search)
