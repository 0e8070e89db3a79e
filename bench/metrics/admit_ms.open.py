"""Server front end (launch/server.py): mean duration of the server's
``ann.admit`` host span (transfer and seeding, bucket pad, beam dispatch of
one request) in the traced segment, in ms."""
from bench.scopes import span_mean


def read(run):
    return span_mean(run, __file__, "ann.admit", scale=1e3)
