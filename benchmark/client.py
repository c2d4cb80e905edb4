"""The load client: one process, one thread, plain HTTP. It never imports
JAX or the program, so the parent keeps the chip and the two do not share
one interpreter lock.

Protocol with the parent (run.py): the spec arrives as the first line of
standard input; the client writes one JSON event per line to standard
output and, where an event needs the parent to act, waits for a line
back on standard input:

    setup_done     -> waits              the parent lets the program's
                                         background compiles finish; the
                                         window opens when it answers
    trace_start    -> waits              the parent starts the profiler
    trace_stop     -> waits              the parent stops it
    window_closed  {t} -> waits          the parent reads device memory
    result         {...}                 everything the metrics need

One cycle: delete the traffic's victims and create its new pods, one
`DELETE` / `PUT /api/v1/resources/pods` call each; run one pass,
`POST /api/v1/schedule?mode=gang&record=0`; read back where the pods
landed, `GET /api/v1/resources/pods`, for the check. The read-back serves
the check and not the load, so the window's clock stops while it runs.
Set-up posts the configuration's scheduler profile, imports the cluster
and runs one cycle of the mix's smallest count: the served program's
shape is the cluster's capacity buckets, not the queue's length, so that
one pass compiles every program the window uses (rehearse.py shows one
program per cell), and the parent then waits out the background compiles
that pass arms. The window then runs whole rounds of the mix
(one cycle of each count) until `--seconds` of its clock have passed;
the round in flight finishes and counts. After the window, every pass is
checked against the plain reference (`references/<name>.py`).
"""

from __future__ import annotations

import argparse
import json
import sys

from harness import Api, HttpError, load_module, now
from traffic import Traffic

SCHEDULE = "/schedule?mode=gang&record=0"
SPANS = ("pass.gang", "pass.encode")  # the program's spans the readers use


def emit(event: str, **data) -> None:
    sys.stdout.write(json.dumps({"event": event, **data}) + "\n")
    sys.stdout.flush()


def wait_parent() -> None:
    if sys.stdin.readline().strip() != "go":
        raise SystemExit("client: the parent did not answer")


def log(msg: str) -> None:
    print(f"client: {msg}", file=sys.stderr, flush=True)


def complete_spans(events: list, names: tuple) -> list:
    """{name, ts, dur} in seconds of each span of `names`: complete (X)
    events as they are, begin/end (B/E) pairs matched per thread."""
    out, open_ = [], {}
    for e in events:
        if e.get("name") not in names:
            continue
        ph, key = e.get("ph"), (e["name"], e.get("tid"))
        if ph == "X":
            out.append({"name": e["name"], "ts": e["ts"] / 1e6, "dur": e.get("dur", 0) / 1e6})
        elif ph == "B":
            open_.setdefault(key, []).append(e["ts"])
        elif ph == "E" and open_.get(key):
            t0 = open_[key].pop()
            out.append({"name": e["name"], "ts": t0 / 1e6, "dur": (e["ts"] - t0) / 1e6})
    return out


class Client:
    def __init__(self, spec: dict, port: int, seed: int, control: bool):
        self.api = Api(port)
        self.config = spec["config"]
        self.traffic = Traffic(spec, seed)
        self.ref = load_module("references", self.config["reference"])
        self.control = self.config["control"] if control else None
        self.live: dict = {}  # name -> manifest
        self.node_of: dict = {}  # name -> node name, None while pending
        self.specs: dict = {}  # name -> reference PodSpec
        self.passes: list = []  # one record per pass, set-up's included
        self.kept: list = []  # (before, after) of each pass, for the check
        self.serial = 0

    def setup(self) -> None:
        nodes, pods = self.traffic.cluster()
        self.cluster = self.ref.Cluster(nodes, self.config)
        self.api.call("POST", "/schedulerconfiguration", self.config["schedulerConfiguration"])
        errors = self.api.call("POST", "/import", {"nodes": nodes, "pods": pods})["errors"]
        if errors:
            raise SystemExit(f"client: import errors {errors[:3]}")
        for p in pods:
            self._track(p)
        log(f"imported {len(nodes)} nodes, {len(pods)} bound pods")
        smallest = min(self.traffic.batches)
        while self.traffic.batches[self.serial % len(self.traffic.batches)] != smallest:
            self.serial += 1
        self.cycle("setup")

    def _track(self, p: dict) -> None:
        name = p["metadata"]["name"]
        self.live[name] = p
        self.node_of[name] = p["spec"].get("nodeName")
        self.specs[name] = self.ref.pod_spec(p)

    def cycle(self, phase: str) -> dict:
        k, deletes, creates = self.traffic.cycle(self.live, self.serial)
        self.serial += 1
        t0 = now()
        for p in deletes:
            self.api.call("DELETE", f"/resources/pods/{p['metadata']['namespace']}/{p['metadata']['name']}")
        for p in creates:
            self.api.call("PUT", "/resources/pods", p)
        t1 = now()
        for p in deletes:
            del self.live[p["metadata"]["name"]], self.node_of[p["metadata"]["name"]]
        for p in creates:
            self._track(p)
        before = dict(self.node_of)
        queued = sum(1 for v in before.values() if v is None)
        decided, failed = self._pass(before, queued)
        t2 = now()
        items = self.api.call("GET", "/resources/pods")["items"]
        t3 = now()
        after = {p["metadata"]["name"]: p["spec"].get("nodeName") or None for p in items}
        self.node_of = {n: after.get(n) for n in self.live}
        self.kept.append((before, after))
        rec = {
            "phase": phase, "count": k, "queued": queued, "decided": decided,
            "failed": failed, "t0": t0, "t_send": t1, "t_recv": t2, "t_end": t3,
        }
        log(f"{phase} {k}: calls {t1 - t0:.3f}s, pass {t2 - t1:.3f}s, read-back {t3 - t2:.3f}s")
        self.passes.append(rec)
        return rec

    def _pass(self, before: dict, queued: int) -> tuple[int, int]:
        """(pods decided, pods of a pass that errored)."""
        if self.control:
            # the reference in the program's place: it decides the queue
            # and binds what it placed, one PUT per pod
            placed = self.ref.schedule(self.cluster, self.specs, before, self.control)
            for name, node in placed.items():
                if node is not None:
                    body = json.loads(json.dumps(self.live[name]))
                    body["spec"]["nodeName"] = node
                    self.api.call("PUT", f"/resources/pods/{body['metadata']['namespace']}/{name}", body)
            return len(placed), 0
        try:
            body = self.api.call("POST", SCHEDULE)
        except HttpError as e:
            log(f"pass failed: {e}")
            return 0, queued
        return int(body["scheduled"]) + int(body["unschedulable"]), 0

    def window(self, seconds: float, trace: bool) -> None:
        batches = len(self.traffic.batches)
        t_open = now()
        paused = 0.0  # the read-backs, which serve the check
        rounds = 0
        traced = False
        while True:
            # trace the second round, or one round past the close when
            # the window held only one
            tracing = trace and rounds == 1
            if tracing:
                emit("trace_start")
                wait_parent()
            for _ in range(batches):
                rec = self.cycle("window")
                paused += rec["t_end"] - rec["t_recv"]
            if tracing:
                emit("trace_stop")
                wait_parent()
                traced = True
            rounds += 1
            if now() - t_open - paused >= seconds:
                break
        self.t_open, self.t_close, self.paused = t_open, now(), paused
        self.metrics_close = self.api.call("GET", "/metrics")["phases"]
        emit("window_closed", t=self.t_close)
        wait_parent()
        if trace and not traced:
            emit("trace_start")
            wait_parent()
            for _ in range(batches):
                self.cycle("traced")
            emit("trace_stop")
            wait_parent()

    def check(self) -> dict:
        """Every pass, set-up's included, against the reference."""
        worst, broken, checked, unverified, reasons = 0, 0, 0, 0, []
        for before, after in self.kept:
            c = self.ref.check_pass(self.cluster, self.specs, before, after)
            worst = max(worst, c.reading())
            broken += c.broken
            checked += c.checked
            unverified += c.unverified
            reasons += c.reasons
        return {
            "gap": worst, "broken": broken, "checked": checked,
            "unverified": unverified, "reasons": reasons[:5], "passes": len(self.kept),
        }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="benchmark load client")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--control", type=int, default=0)
    args = ap.parse_args(argv)
    spec = json.loads(sys.stdin.readline())
    c = Client(spec, args.port, args.seed, bool(args.control))
    c.setup()
    emit("setup_done")
    wait_parent()
    metrics_open = c.api.call("GET", "/metrics")
    c.window(args.seconds, bool(args.trace))
    spans = []
    if args.trace:
        spans = complete_spans(c.api.call("GET", "/debug/trace")["traceEvents"], SPANS)
    c.api.close()
    t0 = now()
    checked = c.check()
    checked["seconds"] = now() - t0
    emit(
        "result",
        passes=c.passes, t_open=c.t_open, t_close=c.t_close, paused=c.paused,
        metrics_open=metrics_open["phases"], metrics_close=c.metrics_close,
        spans=spans, check=checked,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
