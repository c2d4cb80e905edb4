"""Test configuration: force an 8-device virtual CPU platform.

Multi-chip hardware isn't available in CI; all sharding tests run over a
virtual 8-device CPU mesh, which exercises the same pjit/shard_map
partitioning XLA applies on a real TPU slice.
"""

import os

# The test suite builds hundreds of small services; ambient speculative
# background compiles would add nondeterministic work (and wall time) to
# every one of them. Tests that exercise the predictive-compile path opt
# back in by constructing CompileBroker(speculative=True) explicitly.
os.environ.setdefault("KSS_NO_SPECULATIVE_COMPILE", "1")

# Ambient run-supervision settings must not leak into the suite: a shell
# with fault injection or a compile deadline exported would skew every
# test. Tests that exercise the ladder set these with monkeypatch.
for _var in (
    "KSS_FAULT_INJECT",
    "KSS_FAULT_INJECT_SEED",
    "KSS_COMPILE_DEADLINE_S",
    "KSS_COMPILE_RETRIES",
    "KSS_COMPILE_BACKOFF_S",
    "KSS_COMPILE_COOLDOWN_PASSES",
    "KSS_COMPILE_COOLDOWN_TTL_S",
    # the execution ladder + graceful drain (docs/resilience.md):
    # ambient dispatch deadlines / retries / drain budgets would skew
    # every pass; ladder tests set them with monkeypatch
    "KSS_DISPATCH_DEADLINE_S",
    "KSS_DISPATCH_RETRIES",
    "KSS_DRAIN_DEADLINE_S",
    # the flight recorder (utils/telemetry.py): an ambient KSS_TRACE=1
    # would make every test pay span emission (and the off-by-default
    # zero-emission test would fail for the wrong reason)
    "KSS_TRACE",
    "KSS_TRACE_RING_CAP",
    "KSS_TRACE_PROPAGATE",
    # the fleet & memory observatory (utils/fleetstats.py): ambient
    # KSS_FLEET_STATS=1 would make every pass in the suite pay the
    # quality reduction + host fetch, and an ambient headroom floor
    # would silently veto the speculation tests; observatory tests arm
    # these explicitly
    "KSS_FLEET_STATS",
    "KSS_FLEET_RING_CAP",
    "KSS_FLEET_SAMPLE",
    "KSS_SPEC_MEM_HEADROOM_BYTES",
    # the SLO plane (utils/slo.py): ambient arming would make every
    # pass in the suite pay observation + evaluation (and ambient
    # objective/window overrides would skew the state-machine tests);
    # SLO tests arm planes explicitly. KSS_EXEMPLARS is default-ON —
    # scrubbed so a shell exporting KSS_EXEMPLARS=0 can't silently
    # empty the exemplar round-trip tests
    "KSS_SLO",
    "KSS_SLO_OBJECTIVES",
    "KSS_SLO_WINDOW_FAST_S",
    "KSS_SLO_WINDOW_SLOW_S",
    "KSS_SLO_BURN_FAST",
    "KSS_SLO_BURN_SLOW",
    "KSS_SLO_ALERT_FOR_S",
    "KSS_SLO_ALERT_RING_CAP",
    "KSS_EXEMPLARS",
    # the lock-order witness (utils/locking.py): an ambient
    # KSS_LOCK_CHECK=1 would wrap every lock the suite creates; the
    # witness tests arm it explicitly with monkeypatch
    "KSS_LOCK_CHECK",
    # the guarded-state witness + jaxpr auditor (docs/static-analysis.md
    # KSS6xx/KSS7xx): ambient arming would instrument every class /
    # re-trace every program the suite builds; their tests opt in
    "KSS_RACE_CHECK",
    "KSS_RACE_CHECK_SAMPLE",
    "KSS_JAXPR_AUDIT",
    "KSS_LINT_STRICT",
    # the program performance ledger (utils/ledger.py): ambient arming
    # would AOT-probe every program the suite compiles (and sampling
    # would synchronize the async pipeline); ledger tests opt in
    "KSS_PROGRAM_LEDGER",
    "KSS_PROGRAM_TIMING_SAMPLE",
    # the AOT bundle store (utils/bundles.py): ambient arming would
    # serialize every program the suite compiles to a shared directory
    # (and cross-test loads would hide real compile behavior); bundle
    # tests opt in with monkeypatch + tmp_path
    "KSS_AOT_BUNDLES",
    "KSS_BUNDLE_DIR",
    # the continuous-batching plane (server/batchplane.py): ambient
    # KSS_BATCH=1 would route every suite pass through collection
    # windows (latency + a vmapped compile per shape); batching tests
    # arm planes explicitly
    "KSS_BATCH",
    "KSS_BATCH_WINDOW_MS",
    "KSS_BATCH_MAX_WAIT_MS",
    "KSS_BATCH_MAX_SESSIONS",
    # the encoded-cluster dtype policy (engine/encode.py): an ambient
    # KSS_DTYPE_POLICY=packed would re-key every encoding and compile
    # signature the suite pins; packed-policy tests pass the policy (or
    # set the knob) explicitly
    "KSS_DTYPE_POLICY",
    # the gang serving chunk (server/service.py gang_chunk): an ambient
    # override would re-key every gang engine the suite builds (the
    # chunk is part of the compile signature) and skew the dispatch-
    # count pins; chunk tests pass it explicitly
    "KSS_GANG_CHUNK",
    # the session plane (server/sessions.py): ambient admission knobs
    # would change quota/limit behavior under test
    "KSS_MAX_SESSIONS",
    "KSS_MAX_PENDING_PODS_PER_SESSION",
    "KSS_MAX_CONCURRENT_PASSES",
    "KSS_SESSION_IDLE_EVICT_S",
    "KSS_SESSION_DIR",
    "KSS_SSE_MAX_SUBSCRIBERS",
    # the serving fleet (fleet/router.py): an ambient KSS_WORKER_ID
    # would stamp a worker label on every exposition the suite parses;
    # fleet tests set identities with monkeypatch + tmp_path
    "KSS_WORKER_ID",
    "KSS_FLEET_WORKERS",
    "KSS_FLEET_DIR",
    "KSS_FLEET_BASE_PORT",
    "KSS_FLEET_PROBE_INTERVAL_S",
    # the fleet durability plane + router resilience (docs/fleet.md,
    # docs/resilience.md): ambient journaling would add disk writes to
    # every acknowledged mutation in the suite, and ambient breaker/
    # retry/transport overrides would skew the state-machine and
    # re-home tests; durability tests arm these explicitly
    "KSS_FLEET_JOURNAL",
    "KSS_FLEET_JOURNAL_SYNC",
    "KSS_FLEET_REPLICAS",
    "KSS_FLEET_REPLICATE_EVERY_S",
    "KSS_FLEET_REQUEST_TIMEOUT_S",
    "KSS_FLEET_ADOPT_TIMEOUT_S",
    "KSS_FLEET_RETRIES",
    "KSS_FLEET_RETRY_BACKOFF_S",
    "KSS_FLEET_BREAKER_FAILURES",
    "KSS_FLEET_BREAKER_OPEN_S",
    "KSS_FLEET_TRANSPORT",
    "KSS_FLEET_REQUEST_RING_CAP",
):
    os.environ.pop(_var, None)

# Force-set (not setdefault): the suite runs on the CPU backend with an
# 8-device virtual mesh, never on an attached accelerator.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# The EXACT dtype policy (engine/encode.py) needs 64-bit ints/floats for
# bit-parity with the pure-Python oracle on arbitrary quantities.
jax.config.update("jax_enable_x64", True)
# Persistent compilation cache: many tests build fresh engines whose
# programs are HLO-identical (different BatchedScheduler instances can't
# share the in-process jit cache) — dedupe them across tests AND runs.
# Single definition (JAX_COMPILATION_CACHE_DIR when set, else the fixed
# repo-local default) lives in utils/compilecache.py, shared with
# bench.py and tools/.
from kube_scheduler_simulator_tpu.utils.compilecache import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache(min_compile_time_secs=0.3)
