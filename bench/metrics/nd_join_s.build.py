"""Build, construct (core/nndescent.py): device self time of the ops under the
program's ``nd_join`` scope in the traced build (phases 1-3: the sampling,
the neighbour-of-neighbour expansion, the reverse candidates and the pool),
in seconds."""
from bench.scopes import scope_s


def read(run):
    return scope_s(run, __file__, "nd_join")
