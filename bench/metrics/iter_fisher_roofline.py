"""The Iter-Fisher kernels' share of their roofline, in percent: the least
time the bytes they need take at the chip's HBM bandwidth (they do about
one FLOP per byte, so bandwidth bounds them), over their device time."""

import costs


def read(run):
    t = run.trace
    if not t or t["kernel_s"] <= 0:
        return None
    sizes = run.cell.reference.stage_sizes(run.cell.config["model"],
                                           run.cell.stated["plan"]["bounds"])
    need = costs.iter_fisher_bytes_per_round(sizes) * t["rounds"]
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / t["kernel_s"]
