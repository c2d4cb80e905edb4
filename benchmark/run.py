"""One run of one benchmark cell on the chip.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process holds the chip. It serves the real `SimulatorService` and
`SimulatorServer` from a thread, as `chip_smoke.py` does, and starts the
load client (client.py) as a child that never imports JAX. It exits
non-zero, with no result, anywhere but on a TPU with the chips the cell
asks for.

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or with
`--trace 1` its per-layer metrics), `device`, with `--trace 1` also
`breakdown`, and last `checks`: each number compared with its limit.
Each metric is read by `metrics/<name>.py`; what the cell is, its
configuration and its traffic are files found by name (harness.py).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

from harness import BENCH_DIR, ROOT, load_module, load_spec, now  # noqa: E402

# the system under test is the program in the checkout this file sits in
sys.path.insert(1, ROOT)

# fixed paths inside the checkout: the compile cache's path is part of
# its key, and a run writes nowhere else
CACHE_DIR = os.path.join(BENCH_DIR, ".cache", "jax")
TRACE_DIR = os.path.join(BENCH_DIR, ".cache", "trace")


def log(msg: str) -> None:
    print(f"run: {msg}", file=sys.stderr, flush=True)


def require_chip(n_chips: int) -> list:
    import jax

    devices = jax.devices()
    platform = devices[0].platform if devices else "none"
    if platform != "tpu":
        raise SystemExit(f"run: JAX platform is {platform!r} ({len(devices)} device(s)), not 'tpu'")
    if len(devices) < n_chips:
        raise SystemExit(f"run: {len(devices)} TPU device(s), the cell needs {n_chips}")
    return devices


def start_server():
    """Service + server exactly as `python -m ...server` builds them
    (server/__main__.py; copied from chip_smoke.py), on a free port,
    serving from a thread of this process."""
    from kube_scheduler_simulator_tpu.server import config as envconfig
    from kube_scheduler_simulator_tpu.server.httpserver import SimulatorServer
    from kube_scheduler_simulator_tpu.server.service import SimulatorService

    cfg = envconfig.from_env()
    service = SimulatorService(
        initial_config=cfg.initial_scheduler_config,
        external_scheduler_enabled=cfg.external_scheduler_enabled,
    )
    server = SimulatorServer(
        service, host="127.0.0.1", port=0, cors_allowed_origins=cfg.cors_allowed_origins
    )
    server.start()
    return server


def memory_peak(devices) -> "int | None":
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Profiler:
    """A jax.profiler capture of the traced round, reduced in place."""

    def __init__(self):
        self.t0 = self.t1 = None

    def start(self) -> None:
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # a call-level trace of the server would swamp the host
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        self.t0 = now()

    def stop(self) -> None:
        import jax

        self.t1 = now()
        jax.profiler.stop_trace()

    def reduce(self, n_passes: int) -> "dict | None":
        import tracereduce

        try:
            events = tracereduce.events_from_dir(TRACE_DIR)
        finally:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
        return tracereduce.reduce(events, n_passes, self.t1 - self.t0)


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, devices: list,
             control: bool = False, t_start: float = T_START) -> dict:
    """Serve, drive the client to its result, and assemble what the
    metric readers read."""
    server = start_server()
    cmd = [
        sys.executable, os.path.join(BENCH_DIR, "client.py"), "--port", str(server.port),
        "--seed", str(seed), "--seconds", str(seconds), "--trace", str(int(trace)),
    ]
    if control:
        cmd += ["--control", "1"]
    child = subprocess.Popen(cmd, stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    profiler = Profiler() if trace else None
    got: dict = {}
    try:
        child.stdin.write(json.dumps(spec) + "\n")
        child.stdin.flush()
        for line in child.stdout:
            ev = json.loads(line)
            kind = ev["event"]
            if kind == "setup_done":
                # the pass of set-up may have armed background compiles
                # of neighbouring shape buckets (utils/broker.py): they
                # belong to set-up, not to the window
                server.service.scheduler.broker.drain(timeout=600)
                got["t_open"] = now()
                log(f"set-up {got['t_open'] - t_start:.3f}s; window of {seconds}s opens")
            elif kind == "trace_start":
                profiler.start()
            elif kind == "trace_stop":
                profiler.stop()
            elif kind == "window_closed":
                got["memory_peak_bytes"] = memory_peak(devices)
            elif kind == "result":
                got["result"] = ev
                continue
            child.stdin.write("go\n")
            child.stdin.flush()
        child.wait()
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
        server.shutdown()
    if child.returncode != 0 or "result" not in got:
        raise SystemExit(f"run: the client exited {child.returncode} without a result")
    res = got["result"]
    passes = res["passes"]
    n_traced = len(spec["traffic"]["batches"])
    return {
        "spec": spec,
        "setup_s": got["t_open"] - t_start,
        "t_open": res["t_open"],
        "t_close": res["t_close"],
        "window_s": res["t_close"] - res["t_open"] - res["paused"],
        "passes": passes,
        "window": [p for p in passes if p["phase"] == "window"],
        "metrics_open": res["metrics_open"],
        "metrics_close": res["metrics_close"],
        "spans": res["spans"],
        "check": res["check"],
        "memory_peak_bytes": got.get("memory_peak_bytes"),
        "trace": profiler.reduce(n_traced) if profiler else None,
    }


def result_line(run: dict, devices: list, trace: bool) -> dict:
    spec = run["spec"]
    metrics = {}
    for m in spec["per_layer"] if trace else spec["end_to_end"]:
        value = load_module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    limits = spec["config"]["limits"]
    checks = {k: {"value": run["check"][k], "limit": v} for k, v in limits.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    window = run["window"]
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": run["memory_peak_bytes"],
    }
    out = {
        "correct": correct,
        "attempted": sum(p["queued"] for p in window),
        "failed": sum(p["failed"] for p in window),
        "metrics": metrics,
        "device": device,
    }
    if trace and run["trace"] is not None:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        out["breakdown"] = run["trace"]["breakdown"]
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="one run of one benchmark cell on the chip")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="put the configuration's control (the reference with one"
                    " guarantee broken) in the program's place")
    args = ap.parse_args(argv)
    spec = load_spec(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu would log to /tmp/tpu_logs
    os.environ.update(spec["config"]["server_env"])
    if args.trace:
        os.environ["KSS_TRACE"] = "1"
    devices = require_chip(spec["chips"])[: spec["chips"]]
    log(f"{len(devices)} x {devices[0].device_kind} ({devices[0].platform}); "
        f"cell {args.workload}, seed {args.seed}")
    run = run_cell(spec, args.seed, args.seconds, bool(args.trace), devices, bool(args.control))
    out = result_line(run, devices, bool(args.trace))
    c = run["check"]
    log(f"checked {c['passes']} passes, {c['checked']} placed pods read "
        f"({c['unverified']} unverified) in {c['seconds']:.3f}s; broken {c['broken']} "
        f"{c['reasons']}")
    for name, v in out["checks"].items():
        print(f"check {name}: {v['value']} (limit {v['limit']})", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
