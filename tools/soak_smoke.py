"""`make soak-smoke`: the survivable-execution-plane chaos soak.

A randomized (but SEEDED — every schedule derives from ``--seed``)
interleaving of the three disturbance families the execution plane must
survive (docs/resilience.md), each asserted against the one invariant
that matters: the replayable trace stays BYTE-IDENTICAL to an
undisturbed run's, and nothing exits dirty. The lock-order witness
(``KSS_LOCK_CHECK=1``) is armed for the whole soak, so any acquisition
inversion the disturbances provoke fails the run loudly.

Stages:

1. **Clean reference** — the seeded chaos timeline, undisturbed; its
   trace is the byte oracle for every later stage.
2. **Device-fault ladder** — ``device_lost:1.0`` injected at the
   dispatch point: the run must complete on a LOWER rung
   (``deviceFailovers >= 1``, mesh shrink included when >1 device) with
   the oracle trace, never an Abort.
3. **Wedged dispatch** — ``dispatch_hang`` + a tiny
   ``KSS_DISPATCH_DEADLINE_S``: the watchdog must trip, the ladder must
   escalate, the trace must not change.
4. **Randomized kill/resume chain** — the CLI run is cut at
   seeded-random event counts (``--stop-after-events``, the
   deterministic SIGTERM stand-in), each segment exiting 0 (the orderly
   drain contract: Interrupted + final checkpoint = zero loss), each
   partial trace a byte prefix of the oracle, and the final resumed
   trace byte-identical.
5. **Real SIGTERM** — a subprocess CLI run killed with an actual
   ``kill -TERM`` mid-run must drain (exit 0) and resume to the oracle
   trace.
6. **Server drain** — an HTTP server with live sessions drains via
   ``POST /api/v1/admin/drain``: readyz flips to the distinct
   ``draining`` 503, new work sheds with the structured 503, every
   session (default included) snapshots, and a NEW manager over the
   same directory restores them transparently.

Exit 0 on pass; one JSON line on stdout. Seconds-to-minutes on CPU —
wired as ``make soak-smoke``, deliberately NOT tier-1.
"""

from __future__ import annotations

import json
import os
import random
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

# armed BEFORE the package imports so every lock the soak touches is
# witness-wrapped (utils/locking.py decides at lock creation). The
# guarded-state witness (KSS_RACE_CHECK, docs/static-analysis.md
# KSS6xx) rides along: every inferred lock-claimed attribute is
# descriptor-checked for the whole soak — an unguarded access raises
# UnguardedAccess into a stage's problems instead of racing silently
os.environ["KSS_LOCK_CHECK"] = "1"
os.environ["KSS_RACE_CHECK"] = "1"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("KSS_NO_SPECULATIVE_COMPILE", "1")
for _var in ("KSS_FAULT_INJECT", "KSS_DISPATCH_DEADLINE_S",
             "KSS_DISPATCH_RETRIES", "KSS_COMPILE_DEADLINE_S"):
    os.environ.pop(_var, None)

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _chaos_dict() -> dict:
    nodes = [
        {
            "metadata": {"name": f"n{i}"},
            "status": {
                "allocatable": {"cpu": "16", "memory": "32Gi", "pods": "110"}
            },
        }
        for i in range(6)
    ]
    pods = [
        {
            "metadata": {"name": f"seed-{i}"},
            "spec": {
                "nodeName": f"n{i % 6}",
                "containers": [
                    {
                        "name": "c",
                        "resources": {
                            "requests": {"cpu": "100m", "memory": "128Mi"}
                        },
                    }
                ],
            },
        }
        for i in range(33)
    ]
    return {
        "name": "soak-smoke",
        "seed": 23,
        "horizon": 30.0,
        "schedulerMode": "gang",
        "pipeline": "async",
        "snapshot": {"nodes": nodes, "pods": pods},
        "arrivals": [
            {
                "kind": "poisson",
                "rate": 0.5,
                "count": 12,
                "template": {
                    "metadata": {"name": "churn"},
                    "spec": {
                        "containers": [
                            {
                                "name": "c",
                                "resources": {
                                    "requests": {
                                        "cpu": "100m",
                                        "memory": "64Mi",
                                    }
                                },
                            }
                        ]
                    },
                },
            }
        ],
        "faults": [
            {"at": 7.0, "action": "cordon", "node": "n0"},
            {"at": 12.0, "action": "fail", "node": "n1"},
            {"at": 18.0, "action": "recover", "node": "n1"},
            {"at": 24.0, "action": "uncordon", "node": "n0"},
        ],
    }


def _http(method: str, url: str, body=None):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    if data is not None:
        req.add_header("Content-Type", "application/json")
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.status, json.loads(resp.read() or b"{}")
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def main() -> int:
    import argparse
    import contextlib
    import io

    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    rng = random.Random(f"kss-soak:{args.seed}")

    from kube_scheduler_simulator_tpu.lifecycle.__main__ import (
        main as lifecycle_cli,
    )
    from kube_scheduler_simulator_tpu.lifecycle.engine import LifecycleEngine
    from kube_scheduler_simulator_tpu.scenario.chaos import ChaosSpec
    from kube_scheduler_simulator_tpu.utils.compilecache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    problems: list[str] = []
    tmp = tempfile.mkdtemp(prefix="kss-soak-")
    spec_path = os.path.join(tmp, "spec.json")
    with open(spec_path, "w") as f:
        json.dump(_chaos_dict(), f)

    def run_cli(argv: list[str]) -> int:
        with contextlib.redirect_stdout(io.StringIO()):
            return lifecycle_cli(argv)

    # -- stage 1: the undisturbed oracle --------------------------------
    clean = LifecycleEngine(ChaosSpec.from_dict(_chaos_dict()))
    clean_res = clean.run()
    clean_bytes = clean.trace_jsonl().encode()
    if clean_res["phase"] != "Succeeded":
        problems.append(f"clean run phase {clean_res['phase']!r}")
    total_events = clean_res["events"]

    # -- stage 2: device loss walks the ladder, answer unchanged --------
    os.environ["KSS_FAULT_INJECT"] = "device_lost:1.0"
    os.environ["KSS_DISPATCH_RETRIES"] = "1"
    try:
        lost = LifecycleEngine(ChaosSpec.from_dict(_chaos_dict()))
        lost_res = lost.run()
    finally:
        os.environ.pop("KSS_FAULT_INJECT", None)
        os.environ.pop("KSS_DISPATCH_RETRIES", None)
    lost_phases = lost_res["metrics"]["phases"]
    if lost_res["phase"] != "Succeeded":
        problems.append(
            f"device_lost run phase {lost_res['phase']!r} "
            f"({lost_res.get('message', '')})"
        )
    if lost_phases.get("deviceFailovers", 0) < 1:
        problems.append("device_lost:1.0 never reached the CPU rung")
    if lost.trace_jsonl().encode() != clean_bytes:
        problems.append("device_lost run's trace differs from the oracle")

    # -- stage 3: wedged dispatch trips the watchdog --------------------
    os.environ["KSS_FAULT_INJECT"] = "dispatch_hang:100ms"
    os.environ["KSS_DISPATCH_DEADLINE_S"] = "0.02"
    os.environ["KSS_DISPATCH_RETRIES"] = "1"
    try:
        hung = LifecycleEngine(ChaosSpec.from_dict(_chaos_dict()))
        hung_res = hung.run()
    finally:
        for var in ("KSS_FAULT_INJECT", "KSS_DISPATCH_DEADLINE_S",
                    "KSS_DISPATCH_RETRIES"):
            os.environ.pop(var, None)
    hung_phases = hung_res["metrics"]["phases"]
    if hung_res["phase"] != "Succeeded":
        problems.append(f"dispatch_hang run phase {hung_res['phase']!r}")
    if hung_phases.get("dispatchRetries", 0) < 1:
        problems.append("dispatch watchdog never tripped a retry")
    if hung.trace_jsonl().encode() != clean_bytes:
        problems.append("dispatch_hang run's trace differs from the oracle")

    # -- stage 4: seeded kill/resume chain ------------------------------
    ckpt = os.path.join(tmp, "chain.ckpt.json")
    cuts = sorted(rng.sample(range(2, max(3, total_events - 4)), 2))
    segments = 0
    for cut in cuts:
        seg_trace = os.path.join(tmp, f"chain-{segments}.jsonl")
        argv = ["--checkpoint-to", ckpt, "--stop-after-events", str(cut),
                "--trace-out", seg_trace]
        argv = (["--resume", ckpt] if segments else ["--spec", spec_path]) + argv
        rc = run_cli(argv)
        segments += 1
        if rc != 0:
            problems.append(f"chain segment {segments} (cut {cut}) exited {rc}")
        with open(seg_trace, "rb") as f:
            seg_bytes = f.read()
        if not clean_bytes.startswith(seg_bytes):
            problems.append(
                f"chain segment {segments}'s trace is not an oracle prefix"
            )
    final_trace = os.path.join(tmp, "chain-final.jsonl")
    rc = run_cli(["--resume", ckpt, "--trace-out", final_trace])
    if rc != 0:
        problems.append(f"chain final resume exited {rc}")
    with open(final_trace, "rb") as f:
        if f.read() != clean_bytes:
            problems.append("chain's final trace is not byte-identical")

    # -- stage 5: a REAL kill -TERM drains and resumes -------------------
    ckpt2 = os.path.join(tmp, "term.ckpt.json")
    killed_trace = os.path.join(tmp, "term-killed.jsonl")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["KSS_LOCK_CHECK"] = "1"
    env["KSS_RACE_CHECK"] = "1"
    env["KSS_NO_SPECULATIVE_COMPILE"] = "1"
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "kube_scheduler_simulator_tpu.lifecycle",
            "--spec", spec_path, "--checkpoint-to", ckpt2,
            "--checkpoint-every-events", "2", "--trace-out", killed_trace,
        ],
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    # the first periodic checkpoint proves the run is past its imports
    # and the graceful handlers are installed — only then pull the plug
    deadline = time.monotonic() + 300
    while (
        not os.path.exists(ckpt2)
        and proc.poll() is None
        and time.monotonic() < deadline
    ):
        time.sleep(0.2)
    if proc.poll() is None:
        time.sleep(rng.uniform(0.0, 1.0))  # land the signal mid-timeline
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
    out, err = proc.communicate(timeout=300)
    if proc.returncode != 0:
        problems.append(
            f"SIGTERM'd run exited {proc.returncode} "
            f"(stderr tail: {err[-300:].decode(errors='replace')!r})"
        )
    try:
        phase = json.loads(out.decode() or "{}").get("phase")
    except json.JSONDecodeError:
        phase = None
        problems.append("SIGTERM'd run printed no result document")
    if phase == "Succeeded":
        with open(killed_trace, "rb") as f:
            if f.read() != clean_bytes:
                problems.append("un-killed subprocess trace differs")
    else:
        if phase != "Interrupted":
            problems.append(f"SIGTERM'd run phase {phase!r}")
        term_trace = os.path.join(tmp, "term-final.jsonl")
        rc = run_cli(["--resume", ckpt2, "--trace-out", term_trace])
        if rc != 0:
            problems.append(f"post-SIGTERM resume exited {rc}")
        with open(term_trace, "rb") as f:
            if f.read() != clean_bytes:
                problems.append("post-SIGTERM resumed trace differs")

    # -- stage 6: HTTP server drain → restart → transparent restore -----
    from kube_scheduler_simulator_tpu.server.httpserver import SimulatorServer
    from kube_scheduler_simulator_tpu.server.sessions import SessionManager
    from kube_scheduler_simulator_tpu.server.service import SimulatorService

    snap_dir = os.path.join(tmp, "sessions")
    server = SimulatorServer(
        port=0, session_config={"snapshot_dir": snap_dir, "idle_evict_s": 0.0}
    ).start()
    base = f"http://127.0.0.1:{server.port}/api/v1"
    try:
        _, sess = _http("POST", f"{base}/sessions", {"name": "soak"})
        sid = sess["id"]
        _http("PUT", f"{base}/resources/nodes", {
            "metadata": {"name": "srv-n0"},
            "status": {"allocatable": {"cpu": "4", "memory": "8Gi",
                                       "pods": "110"}},
        })
        _http("PUT", f"{base}/sessions/{sid}/resources/pods", {
            "metadata": {"name": "srv-p0"},
            "spec": {"containers": [{"name": "c", "resources": {
                "requests": {"cpu": "100m", "memory": "64Mi"}}}]},
        })
        code, _ = _http("POST", f"{base}/admin/drain")
        if code != 202:
            problems.append(f"admin/drain answered {code}")
        deadline = time.monotonic() + 60
        status: dict = {}
        while time.monotonic() < deadline:
            _, status = _http("GET", f"{base}/admin/drain")
            if status.get("done"):
                break
            time.sleep(0.1)
        if not status.get("done"):
            problems.append("drain never completed")
        code, ready = _http("GET", f"{base}/readyz")
        if code != 503 or ready.get("state") != "draining":
            problems.append(
                f"draining readyz was {code}/{ready.get('state')!r}"
            )
        code, shed = _http("POST", f"{base}/schedule")
        if code != 503 or shed.get("kind") != "ServerDraining":
            problems.append(
                f"draining server answered {code}/{shed.get('kind')!r} "
                f"instead of shedding"
            )
        drained = (status.get("result") or {}).get("drainedSessions") or []
        if "default" not in drained or sid not in drained:
            problems.append(f"drain snapshotted {drained}, expected both")
    finally:
        server.shutdown()
    # "restart": a fresh manager over the same directory adopts the
    # snapshots — the default session's store restores in place
    mgr2 = SessionManager(SimulatorService(), snapshot_dir=snap_dir)
    if mgr2._sessions[  # noqa: SLF001 — white-box by design in the soak
        "default"
    ].service.store.count("nodes") != 1:
        problems.append("restarted default session lost the node")
    restored = mgr2.get(sid)
    if restored.service.store.count("pods") != 1:
        problems.append("restored session lost the pod")
    mgr2.shutdown()

    line = {
        "config": "soak_smoke",
        "seed": args.seed,
        "oracle_events": total_events,
        "device_failovers": lost_phases.get("deviceFailovers", 0),
        "mesh_shrinks": lost_phases.get("meshShrinks", 0),
        "dispatch_retries_hang": hung_phases.get("dispatchRetries", 0),
        "chain_cuts": cuts,
        "sigterm_phase": phase,
        "problems": len(problems),
    }
    print(json.dumps(line), flush=True)
    if problems:
        print("soak-smoke FAILED: " + "; ".join(problems), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
