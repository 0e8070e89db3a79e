"""Server front end (launch/server.py): the 99th percentile of request
latency over every request of the run, timed from when it was due, in ms.
It stands beside ``p50_ms`` as a reading, not a bound: on one v5e the
serving thread stalls for 0.1 s to seconds once in a while, and this tail
follows the stalls (PERF.md)."""


def read(run):
    return run.record.get("p99_ms")
