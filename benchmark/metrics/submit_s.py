"""Client clock: mean seconds per cycle spent on the cycle's per-pod
DELETE and PUT /api/v1/resources/pods calls."""

from readers import mean


def read(run):
    return mean([p["t_send"] - p["t0"] for p in run["window"]])
