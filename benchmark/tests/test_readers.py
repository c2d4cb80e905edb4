"""The end-to-end readers take every pass of the window."""

from __future__ import annotations

from harness import load_module


def _run(rts):
    passes, t = [], 0.0
    for i, rt in enumerate(rts):
        passes.append({"phase": "window", "count": 1, "queued": 10, "decided": 10, "failed": 0,
                       "t0": t, "t_send": t + 0.5, "t_recv": t + 0.5 + rt, "t_end": t + 0.6 + rt})
        t += 0.6 + rt
    paused = 0.1 * len(rts)  # the read-backs
    return {"window": passes, "t_open": 0.0, "t_close": t, "window_s": t - paused, "setup_s": 3.0}


def test_mean_over_all_passes_including_a_stall_at_the_end():
    run = _run([1.0, 1.0, 1.0, 9.0, 9.0])
    assert abs(load_module("metrics", "pass_mean_s").read(run) - 21.0 / 5) < 1e-12


def test_rate_divides_by_the_window_without_its_read_backs():
    run = _run([1.0, 2.0, 30.0])
    rate = load_module("metrics", "pods_per_s").read(run)
    assert abs(rate - 30 / (run["t_close"] - 0.3)) < 1e-12


def test_counter_and_span_readers():
    run = _run([1.0, 1.0])
    run["metrics_open"] = {"fullEncodes": 3, "deltaEncodes": 1, "cachedEncodes": 0,
                           "engineBuilds": 1, "compileMisses": 2, "speculativeCompiles": 1, "decodeSeconds": 1.0,
                           "compileSeconds": 40.0}
    run["metrics_close"] = dict(run["metrics_open"], fullEncodes=5, decodeSeconds=2.0)
    p = run["window"][0]
    run["spans"] = [
        {"name": "pass.gang", "ts": p["t_send"] + 0.1, "dur": 0.8},
        {"name": "pass.encode", "ts": p["t_send"] + 0.1, "dur": 0.5},
    ]
    read = lambda n: load_module("metrics", n).read(run)  # noqa: E731
    assert read("full_encode_share") == 1.0
    assert read("window_compiles") == 0
    assert read("finish_s") == 0.5
    assert read("setup_compile_s") == 40.0
    assert abs(read("http_s") - 0.2) < 1e-9
    assert read("encode_s") == 0.5


def test_program_spans_pair_begin_and_end_per_thread():
    from client import complete_spans

    ev = [
        {"ph": "B", "name": "pass.gang", "ts": 1e6, "tid": 1},
        {"ph": "X", "name": "pass.encode", "ts": 1.1e6, "dur": 2e5, "tid": 1},
        {"ph": "B", "name": "pass.gang", "ts": 1.5e6, "tid": 2},
        {"ph": "E", "name": "pass.gang", "ts": 2e6, "tid": 1},
        {"ph": "E", "name": "pass.gang", "ts": 4e6, "tid": 2},
        {"ph": "X", "name": "http.request", "ts": 0, "dur": 1, "tid": 1},
    ]
    got = sorted((s["name"], s["ts"], s["dur"]) for s in complete_spans(ev, ("pass.gang", "pass.encode")))
    assert got == [("pass.encode", 1.1, 0.2), ("pass.gang", 1.0, 1.0), ("pass.gang", 1.5, 2.5)]
