"""Committed DDPM steps per speculation round of a chain, from the chains'
own counters (a, rounds) at the window's edges: the paper's speedup
K / depth, as a count."""


def read(run):
    w = run["work"]
    return w["steps"] / w["rounds"] if w["rounds"] else None
