"""phases.gangFixpointRounds counted across the window, per pass: the
commit rounds `gang.fixpoint` ran on the chip (booked in server/service.py
`_gang_finish_inner`). A pass's device time grows with its rounds, not
with its shapes."""

from readers import delta


def read(run):
    n = len(run["window"])
    return delta(run, "gangFixpointRounds") / n if n else None
