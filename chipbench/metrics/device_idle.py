"""Share of the traced window in which no operation ran on a device, from
the profiler trace: 1 - busy / window, the mean over the chips used."""


def read(run):
    t = run.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
