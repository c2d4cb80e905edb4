"""phases.decodeSeconds over the window, per pass: the host's wait for the
device's gang.fixpoint AND the store write-back of the placements
(server/service.py `_gang_finish`). The name says no more than that."""

from readers import delta


def read(run):
    n = len(run["window"])
    return delta(run, "decodeSeconds") / n if n else None
