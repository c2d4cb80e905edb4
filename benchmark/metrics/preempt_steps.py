"""phases.gangPreemptSteps counted across the window, per pass: the steps
the served preempt phases ran (engine/gang.py `preempt_phase`, one per pod
that entered a phase; booked in server/service.py `_gang_finish_inner`).
None where the program keeps no such counter."""


def read(run):
    n = len(run["window"])
    key = "gangPreemptSteps"
    if not n or key not in run["metrics_open"] or key not in run["metrics_close"]:
        return None
    return (run["metrics_close"][key] - run["metrics_open"][key]) / n
