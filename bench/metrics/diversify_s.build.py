"""Build, diversify (core/diversify.py): mean seconds of the GD stage per
build, ``BuildReport.wall_diversify_s``."""


def read(run):
    builds = run.record.get("builds") or []
    if not builds:
        return None
    return sum(b["diversify_s"] for b in builds) / len(builds)
