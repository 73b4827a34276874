"""Mean time to build the ranking engine (the benchmark's own span
around ``make_engine``) per job in the window."""


def read(run):
    jobs = run.get("jobs")
    return 1e3 * sum(j["build_s"] for j in jobs) / len(jobs) if jobs else None
