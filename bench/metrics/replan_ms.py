"""Mean host time of the planner per budget switch in the window, in ms
(``SegmentReport.replan_s``)."""


def read(run):
    switches = [s for s in run.window_segments if s.replanned]
    if not switches:
        return None
    return 1e3 * sum(s.replan_s for s in switches) / len(switches)
