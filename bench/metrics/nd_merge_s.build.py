"""Build, construct (core/nndescent.py): device self time of the ops under the
program's ``nd_merge`` scope in the traced build (phases 4b-5: the push-back
scatter and the merge into the k-lists), in seconds."""
from bench.scopes import scope_s


def read(run):
    return scope_s(run, __file__, "nd_merge")
