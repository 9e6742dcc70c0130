"""The whole fit's share of the chip's bf16 peak FLOP/s, in %: the
algorithm's flops of a fit (featurize, and one matvec per PCG iteration,
counted from shapes by ``roofline``) over the window's fit time (host
clock) times the peak.  It bounds what a change to one kernel can gain."""
from chipbench import roofline


def read(run):
    secs, iters, info = run.window.seconds, run.info["pcg_iters"], run.info
    if not iters or sum(secs) <= 0:
        return None
    n, m = info["n"], info["m"]
    flops = len(iters) * roofline.featurize_flops(n, m, info["d"]) + \
        sum(iters) * roofline.matvec_work(n, m, info["k"])[0]
    peak = roofline.peaks(run.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / (sum(secs) * peak)
