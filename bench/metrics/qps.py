"""Requests answered per second: every answer of the window over all of
its time, from the first send to the last answer (the window closes once
every request sent has been answered)."""


def read(run):
    answered, secs = run.get("answered"), run.get("window_s")
    return answered / secs if answered and secs else None
