"""Least time of one matvec at the cell's (n, m, k) on this chip, over the
device time per PCG iteration, in % (trace and the peaks table)."""
from chipbench import harness, roofline


def read(run):
    per_iter_ms = harness.reader("pcg_iter_ms")(run)
    if per_iter_ms is None:
        return None
    info = run.info
    flops, nbytes = roofline.matvec_work(info["n"], info["m"], info["k"])
    least = roofline.least_seconds(flops, nbytes,
                                   roofline.peaks(run.device_kind))
    return least / (per_iter_ms * 1e-3) * 100.0
