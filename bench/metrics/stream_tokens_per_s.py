"""Stream tokens trained per second: the new rows (not the replayed ones)
of every round the plan trained in the window, over the whole window."""


def read(run):
    if run.window_s <= 0 or run.stream_tokens <= 0:
        return None
    return run.stream_tokens / run.window_s
