"""Server front end (launch/server.py): mean duration of the server's
``ann.fetch`` host span (the copies of a finished request's answers to the
host) in the traced segment, in ms."""
from bench.scopes import span_mean


def read(run):
    return span_mean(run, __file__, "ann.fetch", scale=1e3)
