"""Build, construct (core/nndescent.py): mean seconds of the NN-Descent
stage per build, ``BuildReport.wall_construct_s``."""


def read(run):
    builds = run.record.get("builds") or []
    if not builds:
        return None
    return sum(b["construct_s"] for b in builds) / len(builds)
