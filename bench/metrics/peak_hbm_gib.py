"""Peak device memory in use on the fullest chip, read from the runtime's
allocator statistics once the window has closed."""


def read(run):
    return run.peak_bytes / 2**30 if run.peak_bytes > 0 else None
