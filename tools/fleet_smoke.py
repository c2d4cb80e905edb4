"""`make fleet-smoke`: the horizontal serving fleet's end-to-end gate
(docs/fleet.md), on CPU, with REAL spawned worker processes:

Gate A — the shared compile CDN. A 2-worker fleet boots over ONE shared
bundle dir. A session pinned to one worker schedules (compiles + saves
bundles); a session pinned to the OTHER worker schedules the same
shape and must resolve every engine program from the store:
`bundleMisses == 0`, `bundleLoads >= 1` — any worker's compile is every
worker's sub-second warm start.

Gate B — worker death loses nothing. A session writes a sentinel pod,
its owner worker gets `kill -TERM` (the zero-loss drain: snapshots
everything, exits 0), the router detects the death and re-homes the
session to its ring successor — which must answer with the sentinel
intact through the SAME router URL.

Gate C — the rolling restart stays observable. `POST
/api/v1/fleet/roll` restarts the (remaining) fleet one worker at a
time; throughout the roll, `/api/v1/metrics` and `/api/v1/fleet` must
keep answering; afterwards every spawned worker is ready again and the
re-homed session still has its state.

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

from kube_scheduler_simulator_tpu.fleet import FleetRouter  # noqa: E402
from kube_scheduler_simulator_tpu.utils.bundles import (  # noqa: E402
    BUNDLE_SUFFIX,
)

NODE = {
    "metadata": {"name": "fn0"},
    "status": {"allocatable": {"cpu": "8", "memory": "16Gi", "pods": "110"}},
}


def _pod(name):
    return {
        "metadata": {"name": name},
        "spec": {
            "containers": [
                {
                    "name": "c",
                    "resources": {
                        "requests": {"cpu": "250m", "memory": "256Mi"}
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
            return resp.status, json.loads(raw) if raw else None
    except urllib.error.HTTPError as e:
        raw = e.read()
        try:
            return e.code, json.loads(raw) if raw else None
        except json.JSONDecodeError:
            return e.code, None


def _create_session_on(router, target_wid, prefix):
    """Create sessions until one's ring owner is `target_wid` (ids are
    free; the ring decides — a handful of tries suffices)."""
    for i in range(64):
        sid = f"{prefix}-{i}"
        w, placed = router.place_session({"id": sid})
        if w is not None and w.id == target_wid:
            code, doc = _req(router.port, "POST", "/api/v1/sessions", {"id": sid})
            if code != 201:
                raise RuntimeError(f"create {sid} on {target_wid}: {code} {doc}")
            return sid
    raise RuntimeError(f"no id hashed to {target_wid} in 64 tries")


def _schedule_session(router, sid, pods):
    base = f"/api/v1/sessions/{sid}"
    code, _ = _req(router.port, "PUT", f"{base}/resources/nodes", NODE)
    assert code == 201, f"node put: {code}"
    for name in pods:
        code, _ = _req(router.port, "PUT", f"{base}/resources/pods", _pod(name))
        assert code == 201, f"pod put: {code}"
    code, out = _req(router.port, "POST", f"{base}/schedule")
    if code != 200:
        raise RuntimeError(f"schedule on {sid}: {code} {out}")
    return out


def _worker_bundles(router, wid):
    _, doc = _req(router.port, "GET", "/api/v1/metrics")
    wdoc = doc["workers"].get(wid) or {}
    return wdoc.get("bundles") or {}


def _wait(predicate, timeout, what):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = predicate()
        if v:
            return v
        time.sleep(0.25)
    raise RuntimeError(f"timed out waiting for {what}")


def main() -> int:
    problems: list[str] = []
    fleet_dir = tempfile.mkdtemp(prefix="kss-fleet-smoke-")
    cache_dir = tempfile.mkdtemp(prefix="kss-fleet-smoke-cache-")
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        KSS_AOT_BUNDLES="1",
        JAX_COMPILATION_CACHE_DIR=cache_dir,
        KSS_NO_SPECULATIVE_COMPILE="1",
    )
    env.pop("KSS_WORKER_ID", None)  # the router assigns identities
    env.pop("KSS_BUNDLE_DIR", None)  # ONE shared store under fleet_dir

    # spawned children inherit the scrubbed env above, not os.environ
    router = FleetRouter(
        n_workers=2,
        fleet_dir=fleet_dir,
        probe_interval_s=0.5,
        env=env,
    ).start()
    result = {"ok": False}
    try:
        bundle_dir = router.bundle_dir

        # ---- Gate A: the shared compile CDN --------------------------------
        sid_a = _create_session_on(router, "w0", "cdn-a")
        _schedule_session(router, sid_a, [f"ap{i}" for i in range(4)])
        _wait(
            lambda: [
                f
                for f in os.listdir(bundle_dir)
                if f.endswith(BUNDLE_SUFFIX)
            ],
            120,
            "worker w0's bundle saves to land in the shared store",
        )
        sid_b = _create_session_on(router, "w1", "cdn-b")
        _schedule_session(router, sid_b, [f"ap{i}" for i in range(4)])
        b_stats = _worker_bundles(router, "w1")
        if b_stats.get("bundleMisses") != 0:
            problems.append(
                f"gate A: worker w1 compiled engine programs despite the "
                f"shared store: {b_stats}"
            )
        if not b_stats.get("bundleLoads"):
            problems.append(
                f"gate A: worker w1 loaded no bundles: {b_stats}"
            )
        result["gateA"] = {"w1Bundles": b_stats}

        # ---- Gate B: worker death loses nothing ----------------------------
        owner = router.worker_for(sid_b)
        victim_wid = owner.id
        base = f"/api/v1/sessions/{sid_b}"
        code, _ = _req(router.port, "PUT", f"{base}/resources/pods", _pod("sentinel"))
        assert code == 201
        owner.proc.terminate()  # kill -TERM: the zero-loss drain
        _wait(
            lambda: _req(router.port, "GET", "/api/v1/fleet")[1]["sessions"].get(
                sid_b
            )
            not in (None, victim_wid),
            120,
            f"session {sid_b} to re-home off {victim_wid}",
        )
        code, items = _req(router.port, "GET", f"{base}/resources/pods")
        names = (
            {p["metadata"]["name"] for p in items["items"]}
            if code == 200
            else set()
        )
        if code != 200 or "sentinel" not in names:
            problems.append(
                f"gate B: re-homed session lost writes "
                f"(status {code}, pods {sorted(names)})"
            )
        _, fdoc = _req(router.port, "GET", "/api/v1/fleet")
        successor = fdoc["sessions"].get(sid_b)
        result["gateB"] = {
            "victim": victim_wid,
            "successor": successor,
            "rehomedSessions": fdoc["rehomedSessions"],
        }

        # ---- Gate C: rolling restart stays observable ----------------------
        code, doc = _req(router.port, "POST", "/api/v1/fleet/roll")
        if code != 202 or not doc.get("started"):
            problems.append(f"gate C: roll refused: {code} {doc}")
        scrapes = 0
        while True:
            code_m, _ = _req(router.port, "GET", "/api/v1/metrics")
            code_f, fdoc = _req(router.port, "GET", "/api/v1/fleet")
            if code_m != 200 or code_f != 200:
                problems.append(
                    f"gate C: scrape went dark mid-roll "
                    f"(metrics {code_m}, fleet {code_f})"
                )
                break
            scrapes += 1
            if not fdoc["roll"]["rolling"]:
                break
            time.sleep(0.5)
        states = {w["id"]: w["state"] for w in fdoc["workers"]}
        not_ready = sorted(
            wid for wid, st in states.items() if st != "ready"
        )
        if not_ready:
            problems.append(
                f"gate C: workers not ready after the roll: "
                f"{ {w: states[w] for w in not_ready} }"
            )
        code, items = _req(router.port, "GET", f"{base}/resources/pods")
        names = (
            {p["metadata"]["name"] for p in items["items"]}
            if code == 200
            else set()
        )
        if code != 200 or "sentinel" not in names:
            problems.append(
                f"gate C: session state lost across the roll "
                f"(status {code}, pods {sorted(names)})"
            )
        result["gateC"] = {
            "scrapesDuringRoll": scrapes,
            "rolled": fdoc["roll"]["rolled"],
            "workerStates": states,
        }
    finally:
        router.shutdown(drain=True)

    result["ok"] = not problems
    result["problems"] = problems
    print(json.dumps(result), flush=True)
    return 0 if not problems else 1


if __name__ == "__main__":
    sys.exit(main())
