"""phases.gangPreemptVictims counted across the window, per pass: the pods
the served passes evicted by preemption, which the finish deletes from the
store (server/service.py `_gang_finish_inner`, span `pass.victims`). None
where the program keeps no such counter."""


def read(run):
    n = len(run["window"])
    key = "gangPreemptVictims"
    if not n or key not in run["metrics_open"] or key not in run["metrics_close"]:
        return None
    return (run["metrics_close"][key] - run["metrics_open"][key]) / n
