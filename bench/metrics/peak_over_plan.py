"""Peak device memory over the memory the planner budgeted
(``Plan.memory``) for the largest plan in force in the window."""


def read(run):
    if run.plan_memory <= 0:
        return None
    return run.peak_bytes / run.plan_memory
