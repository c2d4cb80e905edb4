"""phases.gangMatchIters counted across the window, per pass: the top-k
matching iterations the gang rounds ran on the chip (engine/gang.py
`match_candidates`, booked in server/service.py `_gang_finish_inner`).
It reads 0 where the matching runs at full width (N bucket 512 or less).
With `device_s` it gives the cost of one iteration. A program without the
counter reads nothing."""

from readers import delta


def read(run):
    n = len(run["window"])
    if not n or "gangMatchIters" not in run["metrics_close"]:
        return None
    return delta(run, "gangMatchIters") / n
