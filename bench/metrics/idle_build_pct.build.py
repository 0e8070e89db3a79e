"""Device: the share of the traced window in which no operation ran on the
chip while one of the build's ``ann.`` host spans (``ann.construct``,
``ann.nd_round``, ``ann.diversify``) was the innermost span open on the host,
in percent (``bench/scopes.py``). It is the part of ``idle_pct.build`` that
the build's own stages hold; the rest lies in the recall proxy, compression
and the probe search."""
from bench.scopes import program_idle_pct


def read(run):
    return program_idle_pct(run, __file__)
