"""Seconds per whole-graph job: all the time of the window over the jobs
completed in it."""


def read(run):
    jobs = run.get("jobs")
    return run["window_s"] / len(jobs) if jobs else None
