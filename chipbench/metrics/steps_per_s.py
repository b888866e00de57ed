"""DDPM steps committed by all chains in the window, over the window's
seconds (host clock; chains still in flight count their progress)."""


def read(run):
    return run["work"]["steps"] / run["window_s"]
