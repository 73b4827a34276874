"""Set-up seconds: from process start to the window, compiles included."""


def read(run):
    return run["setup_s"]
