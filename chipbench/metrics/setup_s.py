"""Set-up: process start to the first timed superstep (host clock)."""


def read(run):
    return run["setup_s"]
