"""Median time of the window's refreshes from the start of the job on the
refresh worker to its result, just before the coreset is staged: the
``select_time_s`` (``RefreshResult.wall_time_s``) of the ``craig_refresh``
events the trainer logged in the window.  It is how stale an installed
coreset is, and it turns into install stall once it outgrows an epoch."""
import statistics


def read(ctx):
    times = [e["select_time_s"] for e in ctx["record"].get("refreshes", [])]
    if not times:
        return None
    return statistics.median(times)
