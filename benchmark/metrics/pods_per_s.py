"""Pods decided (placed or reported unschedulable) by the window's passes,
over the window's seconds: per-pod create and delete calls and passes
included, the check's read-backs, for which the window's clock stops,
left out."""


def read(run):
    decided = sum(p["decided"] for p in run["window"])
    return decided / run["window_s"]
