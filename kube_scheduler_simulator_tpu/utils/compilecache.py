"""The one definition of the repo-local persistent XLA compile cache.

bench.py, tests/conftest.py, the serving path and the tools all want the
same thing: repeat compiles of an identical program (across processes
AND across runs) are disk hits, not fresh XLA compiles.

`cache_dir()` is the one answer to "where": JAX's own
`JAX_COMPILATION_CACHE_DIR` when it is set — so the cache can be placed
from outside — and otherwise a fixed default. The compile cache, the AOT
bundles (utils/bundles.py), the program ledger (utils/ledger.py) and the
KSS715 fingerprint baseline (analysis/jaxpr_audit.py) all live under it.
The path is part of the cache's key, so it never derives from a temp
name, a pid or the time.

The default directory is `.jax_cache` at the repo root (gitignored):
per-checkout isolation — a world-shared /tmp dir would break on
multi-user hosts and let another local user plant crafted cache entries
that deserialize into in-process executables. When the `__file__`-derived
root is NOT a writable checkout (a site-packages install run by an
unprivileged user — ADVICE r5), the default falls back to the per-user
`~/.cache/kss-jax`, which keeps the same single-user isolation property.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def shape_bucket(n: int, lo: int = 8) -> int:
    """Geometric (power-of-two) shape bucket for a live object count.

    Every padded-axis length that reaches XLA — node capacity, pod
    capacity, the sequential scan's queue length — is rounded up to the
    next power of two at or above `lo`, so churn that adds or removes a
    few objects keeps reusing the program compiled for the current
    bucket instead of recompiling per exact count. A bucket is crossed
    (and one recompile paid, amortized by the persistent disk cache
    below) only when the live count doubles past it or shrink passes
    re-encode at a smaller bucket. `n <= 0` maps to 0: an empty axis is
    its own (trivial) shape class, not an 8-wide one.
    """
    if n <= 0:
        return 0
    c = lo
    while c < n:
        c *= 2
    return c


def capacity_buckets(
    n_nodes: int, n_pods: int, *, node_lo: int = 8, pod_lo: int = 8
) -> tuple[int, int]:
    """(node_capacity, pod_capacity) for a cluster of live counts — THE
    bucket policy encode_cluster callers share (server/service.py, the
    delta encoder, benchmarks). Also the bucket component of encoding /
    compiled-program cache keys: two stores whose counts land in the
    same buckets produce shape-identical programs."""
    return (
        max(shape_bucket(n_nodes, node_lo), 1),
        max(shape_bucket(n_pods, pod_lo), 1),
    )


def default_cache_dir(repo_root: "str | None" = None) -> str:
    """The cache directory absent JAX_COMPILATION_CACHE_DIR:
    `<repo_root>/.jax_cache` when the root is a writable directory, else
    the per-user `~/.cache/kss-jax` (the package may live in a read-only
    site-packages tree)."""
    root = _REPO_ROOT if repo_root is None else repo_root
    if os.path.isdir(root) and os.access(root, os.W_OK):
        return os.path.join(root, ".jax_cache")
    return os.path.join(os.path.expanduser("~"), ".cache", "kss-jax")


def cache_dir() -> str:
    """The persistent cache directory: `JAX_COMPILATION_CACHE_DIR` when
    set, else `default_cache_dir()`."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or default_cache_dir()


def enable_compile_cache(min_compile_time_secs: float = 0.1) -> None:
    """Point JAX at the persistent compile cache (`cache_dir()`). Safe to
    call repeatedly; failures are swallowed — the cache is an
    optimization, never a correctness dependency."""
    try:
        import jax

        jax.config.update(  # type: ignore[no-untyped-call]
            "jax_compilation_cache_dir", cache_dir()
        )
        jax.config.update(  # type: ignore[no-untyped-call]
            "jax_persistent_cache_min_compile_time_secs",
            min_compile_time_secs,
        )
    except Exception:  # noqa: BLE001 — cache is an optimization only
        pass
