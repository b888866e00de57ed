"""The grouped matmul kernel's share of its roofline over the traced window.

T is the device seconds of every op named after the kernel (``gmm``, the
name before `` = `` in the trace, XLA's ``.N`` suffix aside).  The work is
the family's count (``gmm_work`` in ``families/<reference>.py``): FLOPs,
the window's model evaluations times a point's FLOPs; bytes, each call's
read of its expert weights (the trace's call count) plus the routed rows
read and written.  The share is the larger of FLOPs over the bf16 peak and
bytes over the HBM peak, over T.  Summed over calls, the larger of the two
sums is at most the sum of each call's larger one, so the share reads at
most what the calls' own roofline times allow.  Nothing where the trace
holds no such op or the family counts no such kernel."""

import re

KERNEL = re.compile(r"%?_?gmm(\.\d+)*")


def read(run):
    from chipbench.model import family

    t = run.get("trace")
    fam = family(run["cfg"])
    if not t or not hasattr(fam, "gmm_work"):
        return None
    ops = [n for n in t["op_s"] if KERNEL.fullmatch(n.split(" = ")[0])]
    seconds = sum(t["op_s"][n] for n in ops)
    evals = run["work"]["evals"]
    if seconds <= 0 or not evals:
        return None
    w = fam.gmm_work(run["cfg"]["model"])
    flops = evals * w["flops_per_point"]
    nbytes = (sum(t["op_n"][n] for n in ops) * w["weight_bytes_per_call"]
              + evals * w["row_bytes_per_point"])
    peak = run["peak"]
    least = max(flops / peak["bf16_flops_per_s"],
                nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
