"""The whole step's share of the chips' bf16 peak: the denoiser points the
chains needed in the window (their model_evals counters) times the FLOPs of
a point (the ``flops`` of the configuration's family), over the window's
seconds, chips and peak."""


def read(run):
    evals = run["work"]["evals"]
    if not evals:
        return None
    flops = evals * run["flops_per_point"]
    return 100.0 * flops / (run["window_s"] * run["chips"]
                            * run["peak"]["bf16_flops_per_s"])
