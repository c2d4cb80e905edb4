"""utils/compilecache.py: the default-directory resolution — repo-local
when the checkout is writable, per-user fallback when not (site-packages
installs, ADVICE r5)."""

import os

from kube_scheduler_simulator_tpu.utils import compilecache


def test_writable_root_uses_repo_local_dir(tmp_path):
    assert compilecache.default_cache_dir(str(tmp_path)) == str(
        tmp_path / ".jax_cache"
    )


def test_unwritable_root_falls_back_to_user_cache(tmp_path):
    ro = tmp_path / "ro"
    ro.mkdir()
    ro.chmod(0o555)
    try:
        got = compilecache.default_cache_dir(str(ro))
    finally:
        ro.chmod(0o755)
    expect = os.path.join(os.path.expanduser("~"), ".cache", "kss-jax")
    # root runs bypass permission bits; accept either resolution there
    if os.access(str(ro), os.W_OK):
        assert got == str(ro / ".jax_cache")
    else:
        assert got == expect


def test_missing_root_falls_back_to_user_cache(tmp_path):
    assert compilecache.default_cache_dir(
        str(tmp_path / "nope")
    ) == os.path.join(os.path.expanduser("~"), ".cache", "kss-jax")


def _fake_jax(monkeypatch) -> dict:
    seen: dict = {}

    class _Cfg:
        @staticmethod
        def update(key, value):
            seen[key] = value

    import types

    monkeypatch.setitem(
        __import__("sys").modules, "jax", types.SimpleNamespace(config=_Cfg())
    )
    return seen


def test_jax_compilation_cache_dir_wins(monkeypatch):
    seen = _fake_jax(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/tmp/elsewhere")
    compilecache.enable_compile_cache()
    assert seen["jax_compilation_cache_dir"] == "/tmp/elsewhere"


def test_default_path_is_fixed(monkeypatch):
    """Unset, the cache lands in the repo's own `.jax_cache` — the same
    path every call, never one derived from a temp name, pid or time."""
    seen = _fake_jax(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    compilecache.enable_compile_cache()
    assert seen["jax_compilation_cache_dir"] == os.path.join(repo, ".jax_cache")
    assert compilecache.cache_dir() == compilecache.cache_dir()


def test_bundles_ledger_and_fingerprints_follow_the_cache_dir(
    monkeypatch, tmp_path
):
    from kube_scheduler_simulator_tpu.analysis import jaxpr_audit
    from kube_scheduler_simulator_tpu.utils import bundles, ledger

    monkeypatch.delenv("KSS_BUNDLE_DIR", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    for path in (
        bundles.bundle_dir(),
        ledger.ledger_path(),
        jaxpr_audit.fingerprint_path(),
    ):
        assert os.path.dirname(path) == str(tmp_path), path
