"""Mean host time of the state remap per budget switch in the window, in
ms (``SegmentReport.remap_s``: host time, not synced with the device)."""


def read(run):
    switches = [s for s in run.window_segments if s.replanned]
    if not switches:
        return None
    return 1e3 * sum(s.remap_s for s in switches) / len(switches)
