"""Engine (core/engine.py): mean distance computations per answered query
row, the engine's own ``n_comps`` counter."""
import numpy as np


def read(run):
    reqs = run.record.get("requests") or []
    if not reqs:
        return None
    return float(np.concatenate([r["n_comps"] for r in reqs]).mean())
