"""Share of the window the trainer spent blocked on the stream source,
in percent: the feeder's own un-overlapped wait (the first, synchronous
take of the run left out)."""


def read(run):
    if run.window_s <= 0:
        return None
    return 100.0 * run.feeder_wait_s / run.window_s
