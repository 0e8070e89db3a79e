"""Device: the share of the traced window in which no operation ran on the
chip, in percent (``bench/trace.py``)."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.window_s())
