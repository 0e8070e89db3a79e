"""Device: the share of the traced window in which no operation ran on the
chip while one of the server's ``ann.`` host spans was the innermost span
open on the host, in percent (``bench/scopes.py``). It is the part of
``idle_pct.open`` that the server's own host work holds."""
from bench.scopes import program_idle_pct


def read(run):
    return program_idle_pct(run, __file__)
