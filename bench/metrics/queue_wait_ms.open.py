"""Server front end (launch/server.py): mean time from when a request was due
to when ``AnnServer`` admitted it, in ms, over the window's answered
requests. Read from the server's own ``t_admit`` timestamps."""
import numpy as np


def read(run):
    reqs = run.record.get("requests") or []
    if not reqs:
        return None
    return float(np.mean([(r["t_admit"] - r["due"]) * 1e3 for r in reqs]))
