"""`make fleet-chaos-smoke`: the fleet durability plane's end-to-end
gate (docs/fleet.md, docs/resilience.md), on CPU, with REAL spawned
worker processes on distinct per-worker session dirs and the HTTP
checkpoint transport forced (`KSS_FLEET_TRANSPORT=http` — the
cross-host behavior; the same-filesystem file move would mask transport
bugs). The lock-order witness (`KSS_LOCK_CHECK=1`) is armed throughout.

Gate A — seeded chaos churn. With `net_drop:0.15,net_delay:10ms` armed
through `POST /api/v1/fleet/faultinject`, a burst of writes goes
through the router: idempotent reads retry through the drops,
non-idempotent writes surface errors honestly — and every write the
router ACKNOWLEDGED must be present afterwards.

Gate B — kill -9 loses nothing acknowledged. A session journals every
acknowledged write (`KSS_FLEET_JOURNAL_SYNC=1` ships each entry to its
ring successors BEFORE the HTTP ack); its owner worker gets `kill -9`
(no drain, no snapshot). The router detects the corpse, promotes the
successor's replica, and the session must answer through the SAME
router URL with a canonically byte-identical resource document — zero
acknowledged-write loss.

Gate C — a net_drop storm opens the breaker. With `net_drop:1.0`, the
per-worker circuit breaker opens after KSS_FLEET_BREAKER_FAILURES
consecutive failures: requests shed 503 + Retry-After WITHOUT touching
a socket. Lifting the storm, the half-open probe closes it and serving
recovers.

Gate D — end-to-end distributed-trace causality (KSS_TRACE=1 armed for
router and workers, docs/observability.md). Under seeded net faults, a
pod is scheduled through the router; the router's merged Perfetto
export (`GET /api/v1/debug/trace`) must then contain ONE trace id
shared by the router request span (with >=1 `router.attempt` child),
the owning worker's pass span, and its `device.execute` span; every
merged interval must be well-formed (`check_nesting` over the merged
document), and some retried GET must show a >=2-attempt span tree.

Exit 0 on pass, 1 with the problem list otherwise; one JSON line either
way.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
import urllib.error
import urllib.request

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

# the witness wraps locks at creation: arm before the package imports
os.environ.setdefault("KSS_LOCK_CHECK", "1")
# gate D's trace plane: the router process records its own span ring
# and propagates trace context on every proxied hop
os.environ["KSS_TRACE"] = "1"

from kube_scheduler_simulator_tpu.fleet import FleetRouter  # noqa: E402
from kube_scheduler_simulator_tpu.lifecycle.checkpoint import (  # noqa: E402
    canonical_bytes,
)
from kube_scheduler_simulator_tpu.utils import telemetry  # noqa: E402


def _pod(name):
    return {
        "metadata": {"name": name},
        "spec": {
            "containers": [
                {
                    "name": "c",
                    "resources": {
                        "requests": {"cpu": "100m", "memory": "128Mi"}
                    },
                }
            ]
        },
    }


def _req(port, method, path, body=None, timeout=600):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        method=method,
        data=json.dumps(body).encode() if body is not None else None,
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            raw = resp.read()
            return resp.status, json.loads(raw) if raw else None, dict(
                resp.headers
            )
    except urllib.error.HTTPError as e:
        raw = e.read()
        try:
            return e.code, json.loads(raw) if raw else None, dict(e.headers)
        except json.JSONDecodeError:
            return e.code, None, dict(e.headers)


def _wait(predicate, timeout, what):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = predicate()
        if v:
            return v
        time.sleep(0.25)
    raise RuntimeError(f"timed out waiting for {what}")


def _canonical_pods(port, sid):
    code, items, _ = _req(port, "GET", f"/api/v1/sessions/{sid}/resources/pods")
    if code != 200:
        return code, None
    return code, canonical_bytes(items)


def main() -> int:
    problems: list[str] = []
    fleet_dir = tempfile.mkdtemp(prefix="kss-chaos-smoke-")
    cache_dir = tempfile.mkdtemp(prefix="kss-chaos-smoke-cache-")
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        KSS_LOCK_CHECK="1",
        KSS_NO_SPECULATIVE_COMPILE="1",
        JAX_COMPILATION_CACHE_DIR=cache_dir,
        # the durability plane under test: per-write journaling with
        # inline successor shipping, a fast replication cadence, and
        # the HTTP transport forced (distinct non-shared session dirs)
        KSS_FLEET_TRANSPORT="http",
        KSS_FLEET_JOURNAL="1",
        KSS_FLEET_JOURNAL_SYNC="1",
        KSS_FLEET_REPLICAS="1",
        KSS_FLEET_REPLICATE_EVERY_S="0.3",
        # resilience knobs sized for a fast smoke
        KSS_FLEET_BREAKER_OPEN_S="0.5",
        KSS_FLEET_RETRY_BACKOFF_S="0.02",
        # gate D: span rings + trace propagation on every process
        KSS_TRACE="1",
    )
    env.pop("KSS_WORKER_ID", None)  # the router assigns identities
    env.pop("KSS_SESSION_DIR", None)  # per-worker dirs under fleet_dir

    router = FleetRouter(
        n_workers=3,
        fleet_dir=fleet_dir,
        probe_interval_s=0.5,
        env=env,
    ).start()
    result = {"ok": False}
    try:
        port = router.port

        # ---- Gate A: seeded chaos churn ------------------------------------
        code, doc, _ = _req(
            port, "POST", "/api/v1/sessions", {"id": "churn-1"}
        )
        assert code == 201, f"create churn-1: {code} {doc}"
        code, doc, _ = _req(
            port,
            "POST",
            "/api/v1/fleet/faultinject",
            {"spec": "net_drop:0.15,net_delay:10ms", "seed": 42},
        )
        if code != 200 or not doc.get("active"):
            problems.append(f"gate A: faultinject refused: {code} {doc}")
        acked: list[str] = []
        errors = 0
        for i in range(30):
            name = f"cp{i}"
            code, _, _ = _req(
                port,
                "PUT",
                "/api/v1/sessions/churn-1/resources/pods",
                _pod(name),
                timeout=30,
            )
            if code == 201:
                acked.append(name)
            else:
                errors += 1
        code, doc, _ = _req(
            port, "POST", "/api/v1/fleet/faultinject", {"spec": ""}
        )
        assert code == 200 and not doc.get("active"), "disarm failed"
        # reads may need the breaker to recover from the churn's drops
        time.sleep(0.6)
        items = _wait(
            lambda: _req(
                port, "GET", "/api/v1/sessions/churn-1/resources/pods"
            )[1]
            if _req(port, "GET", "/api/v1/sessions/churn-1/resources/pods")[0]
            == 200
            else None,
            30,
            "churn session to answer after the storm",
        )
        present = {p["metadata"]["name"] for p in items["items"]}
        lost = [n for n in acked if n not in present]
        if lost:
            problems.append(
                f"gate A: acknowledged writes lost in churn: {lost}"
            )
        if not acked:
            problems.append(
                "gate A: chaos dropped every write — nothing was exercised"
            )
        _, fdoc, _ = _req(port, "GET", "/api/v1/fleet")
        result["gateA"] = {
            "acked": len(acked),
            "writeErrors": errors,
            "routerRetries": fdoc.get("retries"),
        }

        # ---- Gate B: kill -9 loses nothing acknowledged --------------------
        code, doc, _ = _req(
            port, "POST", "/api/v1/sessions", {"id": "crash-1"}
        )
        assert code == 201, f"create crash-1: {code} {doc}"
        for i in range(5):
            code, _, _ = _req(
                port,
                "PUT",
                "/api/v1/sessions/crash-1/resources/pods",
                _pod(f"base{i}"),
            )
            assert code == 201, f"base write {i}: {code}"
        # let the ticker ship the base unit to the ring successor; the
        # tail below then rides the sync journal ship alone
        time.sleep(1.0)
        for i in range(3):
            code, _, _ = _req(
                port,
                "PUT",
                "/api/v1/sessions/crash-1/resources/pods",
                _pod(f"tail{i}"),
            )
            assert code == 201, f"tail write {i}: {code}"
        code, before = _canonical_pods(port, "crash-1")
        assert code == 200
        victim = router.worker_for("crash-1")
        victim_wid = victim.id
        victim.proc.kill()  # kill -9: no drain, no snapshot, no goodbye
        _wait(
            lambda: _req(port, "GET", "/api/v1/fleet")[1]["sessions"].get(
                "crash-1"
            )
            not in (None, victim_wid),
            120,
            f"crash-1 to re-home off {victim_wid}",
        )
        _wait(
            lambda: _canonical_pods(port, "crash-1")[0] == 200,
            60,
            "the re-homed session to answer",
        )
        code, after = _canonical_pods(port, "crash-1")
        if before != after:
            problems.append(
                "gate B: re-homed document differs from the pre-kill "
                "acknowledged state (acknowledged-write loss)"
            )
        _, fdoc, _ = _req(port, "GET", "/api/v1/fleet")
        if fdoc.get("pendingAdopts"):
            problems.append(
                f"gate B: adoptions left pending: {fdoc['pendingAdopts']}"
            )
        result["gateB"] = {
            "victim": victim_wid,
            "successor": fdoc["sessions"].get("crash-1"),
            "rehomedSessions": fdoc.get("rehomedSessions"),
        }

        # ---- Gate C: a net_drop storm opens the breaker --------------------
        code, doc, _ = _req(
            port,
            "POST",
            "/api/v1/fleet/faultinject",
            {"spec": "net_drop:1.0", "seed": 7},
        )
        assert code == 200 and doc.get("active"), "storm arm failed"
        saw_shed = saw_retry_after = False
        for _ in range(20):
            code, doc, headers = _req(
                port,
                "GET",
                "/api/v1/sessions/crash-1/resources/pods",
                timeout=30,
            )
            if code == 503:
                saw_shed = True
                if headers.get("Retry-After"):
                    saw_retry_after = True
                if (doc or {}).get("kind") == "CircuitOpen":
                    break
        if not saw_shed:
            problems.append("gate C: total net_drop never shed a request")
        if not saw_retry_after:
            problems.append("gate C: sheds carried no Retry-After")
        _, fdoc, _ = _req(port, "GET", "/api/v1/fleet")
        if not fdoc.get("breakerOpens"):
            problems.append(
                f"gate C: breaker never opened (doc: {fdoc.get('workers')})"
            )
        code, doc, _ = _req(
            port, "POST", "/api/v1/fleet/faultinject", {"spec": ""}
        )
        assert code == 200 and not doc.get("active"), "storm disarm failed"
        time.sleep(0.6)  # past KSS_FLEET_BREAKER_OPEN_S
        _wait(
            lambda: _req(
                port, "GET", "/api/v1/sessions/crash-1/resources/pods"
            )[0]
            == 200,
            30,
            "the breaker's half-open probe to close it",
        )
        _, fdoc, _ = _req(port, "GET", "/api/v1/fleet")
        owner = fdoc["sessions"].get("crash-1")
        breakers = {w["id"]: w["breaker"] for w in fdoc["workers"]}
        if breakers.get(owner) != "closed":
            problems.append(
                f"gate C: owner breaker not closed after recovery: {breakers}"
            )
        result["gateC"] = {
            "breakerOpens": fdoc.get("breakerOpens"),
            "breakers": breakers,
        }

        # ---- Gate D: end-to-end distributed-trace causality ----------------
        code, doc, _ = _req(port, "POST", "/api/v1/sessions", {"id": "trace-1"})
        assert code == 201, f"create trace-1: {code} {doc}"
        base = "/api/v1/sessions/trace-1"
        code, _, _ = _req(
            port,
            "PUT",
            f"{base}/resources/nodes",
            {
                "metadata": {"name": "tn0"},
                "status": {
                    "allocatable": {
                        "cpu": "8", "memory": "16Gi", "pods": "110"
                    }
                },
            },
        )
        assert code == 201, f"trace node: {code}"
        code, _, _ = _req(
            port, "PUT", f"{base}/resources/pods", _pod("tp0")
        )
        assert code == 201, f"trace pod: {code}"
        # seeded net faults: idempotent GETs retry through the drops
        # (the >=2-attempt span tree); the schedule POST is single-
        # attempt per inbound request, retried here at the client
        code, doc, _ = _req(
            port,
            "POST",
            "/api/v1/fleet/faultinject",
            {"spec": "net_drop:0.3", "seed": 11},
        )
        assert code == 200 and doc.get("active"), "gate D arm failed"
        scheduled = False
        for _ in range(25):
            code, sdoc, _ = _req(port, "POST", f"{base}/schedule", timeout=60)
            if code == 200 and (sdoc or {}).get("scheduled"):
                scheduled = True
                break
        for _ in range(15):
            _req(port, "GET", f"{base}/resources/pods", timeout=30)
        code, doc, _ = _req(
            port, "POST", "/api/v1/fleet/faultinject", {"spec": ""}
        )
        assert code == 200 and not doc.get("active"), "gate D disarm failed"
        if not scheduled:
            problems.append("gate D: pod never scheduled through the storm")
        # the request ring names the schedule request's trace id and
        # the retried GETs' attempt counts
        _, ring, _ = _req(port, "GET", "/api/v1/fleet/requests")
        entries = (ring or {}).get("requests") or []
        sched = [
            e
            for e in entries
            if e.get("route") == f"{base}/schedule" and e.get("status") == 200
        ]
        retried_gets = [
            e
            for e in entries
            if e.get("method") == "GET" and (e.get("attempts") or 0) >= 2
        ]
        tid = sched[-1]["trace"] if sched else None
        if tid is None:
            problems.append(
                "gate D: request ring has no traced 200 schedule entry"
            )
        if not retried_gets:
            problems.append(
                "gate D: no GET retried under the seeded drops "
                "(no >=2-attempt span tree to check)"
            )
        _, merged, _ = _req(port, "GET", "/api/v1/debug/trace")
        events = (merged or {}).get("traceEvents") or []
        other = (merged or {}).get("otherData") or {}
        if not other.get("merged") or not other.get("tracingEnabled"):
            problems.append(f"gate D: merged export not armed: {other}")
        if len(other.get("tracks") or []) < 3:
            problems.append(
                f"gate D: expected router + >=2 worker tracks, got "
                f"{other.get('tracks')}"
            )
        try:
            telemetry.check_nesting(
                events, dropped=int(other.get("droppedEvents") or 0)
            )
        except ValueError as e:
            problems.append(f"gate D: merged intervals malformed: {e}")

        def _with_trace(t):
            return [
                ev
                for ev in events
                if (ev.get("args") or {}).get("trace") == t
            ]

        if tid is not None:
            tev = _with_trace(tid)
            req_spans = [
                ev
                for ev in tev
                if ev.get("name") == "router.request" and ev.get("ph") == "B"
            ]
            attempt_spans = [
                ev
                for ev in tev
                if ev.get("name") == "router.attempt" and ev.get("ph") == "B"
            ]
            pass_spans = [
                ev
                for ev in tev
                if str(ev.get("name", "")).startswith("pass.")
                and ev.get("pid") != 0
            ]
            device_spans = [
                ev
                for ev in tev
                if ev.get("name") == "device.execute" and ev.get("pid") != 0
            ]
            if not req_spans:
                problems.append(
                    "gate D: no router.request span carries the "
                    "scheduled pod's trace id"
                )
            if not attempt_spans:
                problems.append(
                    "gate D: the traced request has no router.attempt child"
                )
            if not pass_spans:
                problems.append(
                    "gate D: no worker pass span carries the trace id "
                    "(context not adopted at the HTTP chokepoint?)"
                )
            if not device_spans:
                problems.append(
                    "gate D: no device.execute span carries the trace id"
                )
            result["gateD"] = {
                "trace": tid,
                "attemptSpans": len(attempt_spans),
                "passSpans": len(pass_spans),
                "deviceSpans": len(device_spans),
                "retriedGets": len(retried_gets),
                "tracks": other.get("tracks"),
            }
        if retried_gets:
            rtid = retried_gets[-1].get("trace")
            r_attempts = [
                ev
                for ev in _with_trace(rtid)
                if ev.get("name") == "router.attempt" and ev.get("ph") == "B"
            ]
            if len(r_attempts) < 2:
                problems.append(
                    f"gate D: retried GET trace {rtid} shows "
                    f"{len(r_attempts)} attempt span(s), expected >=2"
                )
    finally:
        router.shutdown(drain=True)

    result["ok"] = not problems
    result["problems"] = problems
    print(json.dumps(result), flush=True)
    return 0 if not problems else 1


if __name__ == "__main__":
    sys.exit(main())
