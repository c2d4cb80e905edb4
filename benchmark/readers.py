"""What the metric readers (`metrics/<name>.py`) share: the window's
passes, the program's spans matched to them, and counter deltas across
the window. Each reader's `read(run)` returns a number, or None when the
run holds nothing for it to read."""

from __future__ import annotations


def window(run: dict) -> list:
    return run["window"]


def spans_in_passes(run: dict, name: str) -> list:
    """(pass, [spans of `name` inside that pass's round trip]) for each
    pass of the window."""
    spans = [s for s in run["spans"] if s["name"] == name]
    out = []
    for p in window(run):
        inside = [s for s in spans if p["t_send"] <= s["ts"] and s["ts"] + s["dur"] <= p["t_recv"]]
        out.append((p, inside))
    return out


def delta(run: dict, key: str) -> float:
    return run["metrics_close"][key] - run["metrics_open"][key]


def mean(xs: list) -> "float | None":
    return sum(xs) / len(xs) if xs else None
