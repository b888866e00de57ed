"""Host milliseconds per superstep boundary: the engine's own dispatch and
harvest timers (EngineStats dispatch_s, fused_dispatch_s, host_sync_s),
their change over the window, per superstep the window drove."""


def read(run):
    if not run["supersteps"]:
        return None
    return run["host_s"] * 1e3 / run["supersteps"]
