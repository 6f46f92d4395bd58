"""Model FLOP utilization of the whole step, in percent: the forward and
backward FLOPs of every round the plan trained in the window (new and
replayed rows; rounds a plan skips count nothing), counted by the cell's
reference (``train_flops``), over the window and the chips' bf16 peak."""


def read(run):
    trained = sum(s.trained_rounds for s in run.window_segments)
    if run.window_s <= 0 or trained <= 0:
        return None
    tr = run.cell.traffic
    rows = tr["batch"] + tr["replay_rows"]
    flops = run.cell.reference.train_flops(run.cell.config["model"], rows, tr["seq"])
    achieved = flops * trained / run.window_s
    return 100.0 * achieved / (run.chips * run.peaks["bf16_flops_per_s"])
