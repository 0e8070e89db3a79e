"""Build, construct (core/nndescent.py): device self time of the ops under the
program's ``nd_score`` scope in the traced build (phase 4: the chunked
gather-kernel scoring of the candidate pool), in seconds."""
from bench.scopes import scope_s


def read(run):
    return scope_s(run, __file__, "nd_score")
