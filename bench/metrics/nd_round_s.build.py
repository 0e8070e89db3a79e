"""Build, construct (core/nndescent.py): mean duration of the program's
``ann.nd_round`` host span (one NN-Descent round: its dispatch, the round on
the device and the read of its update count) in the traced build, in
seconds."""
from bench.scopes import span_mean


def read(run):
    return span_mean(run, __file__, "ann.nd_round")
