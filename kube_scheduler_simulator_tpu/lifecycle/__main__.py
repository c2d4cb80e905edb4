"""CLI: ``python -m kube_scheduler_simulator_tpu.lifecycle``.

Modes over one ChaosSpec file (JSON or YAML):

  * default        — run the discrete-event timeline (engine.py); the
    result document prints to stdout, the replayable JSONL trace lands
    at ``--trace-out`` when given, and ``--perfetto-out`` exports the
    run's span flight recording as Chrome trace-event JSON
    (docs/observability.md);
  * ``--sweep S``  — additionally run the vmapped fault sweep
    (faultsweep.py) over the spec's snapshot cluster: S sampled failure
    scenarios at ``--fail-prob``, seeded from the spec;
  * ``--resume CKPT`` — continue a run from a checkpoint written by
    ``--checkpoint-to`` (docs/resilience.md): the trace written to
    ``--trace-out`` is the FULL trace (checkpointed prefix + new
    suffix), byte-identical to an uninterrupted run's.

Run supervision: with ``--checkpoint-to`` the engine persists an atomic
checkpoint every ``--checkpoint-every-events`` timeline events and/or
``--checkpoint-every-sim-s`` simulated seconds, and SIGINT/SIGTERM stop
the run gracefully at the next batch boundary with a FINAL checkpoint
(phase ``Interrupted``, exit code 1) — a second signal falls through to
the default handler for a hard kill. ``--stop-after-events K`` is the
deterministic stand-in for that kill (tools/resilience_smoke.py).

Exit code 0 on a Succeeded run — and ALSO on an ``Interrupted`` run
that wrote its final checkpoint: a graceful SIGTERM with checkpointing
configured is the ORDERLY drain path (docs/resilience.md), and an
orderly drain that lost nothing must read as success to a supervisor
driving rolling restarts. Any other outcome exits 1 (the KEP-184
runner's contract, same as scenario/batch.py).

Device init happens at boot, as in the serving shell
(server/__main__.py): a backend that cannot start fails the run there.
"""

from __future__ import annotations

import json
import signal
import sys


def _load_spec(path: str) -> dict:
    with open(path) as f:
        if path.endswith((".yaml", ".yml")):
            import yaml

            return yaml.safe_load(f)
        return json.load(f)


def main(argv: "list[str] | None" = None) -> int:
    import argparse

    # strict KSS_* validation first: a malformed KSS_FAULT_INJECT (or
    # any typo'd knob) fails the run HERE with a clear message instead
    # of mid-timeline at the first fire point (utils/envcheck.py)
    from ..utils import envcheck

    envcheck.fail_fast()

    ap = argparse.ArgumentParser(
        prog="kube_scheduler_simulator_tpu.lifecycle",
        description="Cluster-lifecycle chaos runner (discrete-event churn, "
        "fault injection, vmapped failure sweeps).",
    )
    ap.add_argument(
        "--spec", help="ChaosSpec file (json/yaml); required unless --resume"
    )
    ap.add_argument(
        "--resume", metavar="CKPT",
        help="continue the run captured in this checkpoint file "
        "(--checkpoint-to output); --spec is ignored — the checkpoint "
        "carries its spec by value",
    )
    ap.add_argument(
        "--trace-out", help="write the replayable JSONL event trace here "
        "(on --resume: the FULL trace, checkpointed prefix included)"
    )
    ap.add_argument(
        "--perfetto-out", metavar="FILE",
        help="write the run's span flight recording here as Chrome "
        "trace-event JSON, loadable in https://ui.perfetto.dev "
        "(docs/observability.md); forces tracing ON for the run even "
        "without KSS_TRACE=1",
    )
    ap.add_argument(
        "--checkpoint-to", metavar="PATH",
        help="persist atomic run checkpoints here (periodic per the "
        "--checkpoint-every-* cadence; final on SIGINT/SIGTERM or "
        "--stop-after-events)",
    )
    ap.add_argument(
        "--checkpoint-every-events", type=int, default=0, metavar="K",
        help="checkpoint every K timeline events (0 = off)",
    )
    ap.add_argument(
        "--checkpoint-every-sim-s", type=float, default=0.0, metavar="N",
        help="checkpoint every N simulated seconds (0 = off)",
    )
    ap.add_argument(
        "--stop-after-events", type=int, default=0, metavar="K",
        help="stop gracefully (final checkpoint, phase Interrupted) after "
        "K timeline events — the deterministic mid-run-kill stand-in",
    )
    ap.add_argument(
        "--pipeline", choices=("sync", "async"), default=None,
        help="override the spec's dispatch pipeline: async overlaps "
        "device execution with event application (byte-identical trace)",
    )
    ap.add_argument(
        "--sweep", type=int, default=0, metavar="S",
        help="also run a vmapped fault sweep over S sampled scenarios",
    )
    ap.add_argument(
        "--fail-prob", type=float, default=0.1,
        help="per-node failure probability for --sweep (default 0.1)",
    )
    args = ap.parse_args(argv)
    if not args.spec and not args.resume:
        ap.error("one of --spec / --resume is required")
    if (
        args.checkpoint_every_events or args.checkpoint_every_sim_s
    ) and not args.checkpoint_to:
        # a run the operator BELIEVES is checkpointing but isn't is the
        # worst outcome of a flag typo — refuse up front
        ap.error("--checkpoint-every-* requires --checkpoint-to")

    from ..scenario.chaos import ChaosSpec
    from ..utils import telemetry
    from ..utils.ledger import COLD_START
    from .checkpoint import load_checkpoint
    from .engine import LifecycleEngine

    # device init at boot, with no fallback (see the module docstring);
    # cold-start phase accounting (utils/ledger.py) marks it
    import jax

    jax.devices()
    COLD_START.mark("bootProbe")

    # --perfetto-out forces the flight recorder on for this run; an
    # env-armed recorder (KSS_TRACE=1) is reused so the export carries
    # whatever was already recorded
    recorder = telemetry.active()
    if args.perfetto_out and recorder is None:
        recorder = telemetry.SpanRecorder()
        telemetry.activate(recorder)

    supervise = dict(
        checkpoint_path=args.checkpoint_to,
        checkpoint_every_events=args.checkpoint_every_events,
        checkpoint_every_sim_s=args.checkpoint_every_sim_s,
        stop_after_events=args.stop_after_events,
    )
    if args.resume:
        engine = LifecycleEngine.from_checkpoint(
            load_checkpoint(args.resume), pipeline=args.pipeline, **supervise
        )
        spec = engine.spec
    else:
        spec = ChaosSpec.from_dict(_load_spec(args.spec))
        engine = LifecycleEngine(spec, pipeline=args.pipeline, **supervise)

    # graceful shutdown: first SIGINT/SIGTERM stops at the next batch
    # boundary (final checkpoint, nothing extra in the trace); a second
    # one restores the default handler's hard behavior
    def _graceful(signum, frame):
        engine.request_stop()
        signal.signal(signum, signal.SIG_DFL)

    prev_handlers = {}
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            prev_handlers[sig] = signal.signal(sig, _graceful)
        except ValueError:  # non-main thread (embedded use): skip
            pass

    try:
        result = engine.run()
    finally:
        for sig, h in prev_handlers.items():
            try:
                signal.signal(sig, h)
            except ValueError:
                pass
    if args.trace_out:
        with open(args.trace_out, "w") as f:
            f.write(engine.trace_jsonl())
        result["traceFile"] = args.trace_out
    if args.perfetto_out:
        n = telemetry.dump_chrome_trace(args.perfetto_out, recorder)
        result["perfettoFile"] = args.perfetto_out
        result["perfettoEvents"] = n

    if args.sweep > 0:
        from ..sched.config import SchedulerConfiguration
        from .faultsweep import FaultSweep

        # sweep the POST-RUN cluster: the timeline's surviving placements
        # are exactly the state whose disruption profile matters
        cfg = (
            SchedulerConfiguration.from_dict(spec.scheduler_config)
            if spec.scheduler_config
            else SchedulerConfiguration.default()
        )
        store = engine.store
        nodes = store.list("nodes")
        pods = store.list("pods")
        if nodes and pods:
            sweep = FaultSweep.from_cluster(
                nodes, pods, cfg,
                priorityclasses=store.list("priorityclasses"),
                namespaces=store.list("namespaces"),
                pvcs=store.list("pvcs"),
                pvs=store.list("pvs"),
                storageclasses=store.list("storageclasses"),
            )
            masks = sweep.sample_masks(args.sweep, spec.seed, args.fail_prob)
            profile = sweep.run(masks)
            profile.pop("assignments")  # tensors don't print
            result["faultSweep"] = profile
        else:
            result["faultSweep"] = {
                "scenarios": 0,
                "message": "post-run cluster has no nodes or no pods",
            }

    from ..utils.broker import jaxpr_audit_enabled

    if jaxpr_audit_enabled():
        # KSS7xx (docs/static-analysis.md): persist this run's compile
        # fingerprints next to the compile cache and surface the audit
        # verdict in the headline — drift against the previous baseline
        # and any program-contract finding turn the run's summary red
        # without failing the run (the tier-1 gate asserts on them)
        from ..analysis.jaxpr_audit import AUDITOR

        drift = AUDITOR.persist()
        audit_findings = AUDITOR.findings()
        result["jaxprAudit"] = {
            "programs": len(AUDITOR.records),
            "findings": [f.render() for f in audit_findings],
            "fingerprintDrift": [f.message for f in drift],
        }

    from ..utils import ledger as ledger_mod

    if ledger_mod.ledger_enabled():
        # the program performance ledger (docs/observability.md): like
        # the fingerprint baseline above, an armed run auto-persists
        # next to the compile cache and surfaces the regression diff
        # in its headline without failing the run (`analysis
        # ledger-diff` is the gating entry point)
        ledger_drift = ledger_mod.LEDGER.persist()
        result["programLedger"] = {
            "programs": ledger_mod.LEDGER.totals()["count"],
            "path": ledger_mod.ledger_path(),
            "drift": [f.render() for f in ledger_drift],
            "coldStart": COLD_START.snapshot(),
        }

    json.dump(result, sys.stdout, indent=2, sort_keys=True)
    print()
    phase = result.get("phase")
    if phase == "Succeeded":
        return 0
    if phase == "Interrupted" and result.get("checkpoint"):
        # the orderly drain: a graceful stop whose final checkpoint
        # landed lost NOTHING — resume reproduces the uninterrupted
        # trace byte-for-byte (docs/resilience.md). Exit 0 so rolling
        # restarts read as success, like the serving shell's SIGTERM.
        return 0
    return 1


if __name__ == "__main__":
    raise SystemExit(main())
