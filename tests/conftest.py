# NOTE: do NOT set --xla_force_host_platform_device_count here — smoke
# tests and benches must see 1 device; only launch/dryrun.py uses 512.
import os

import pytest


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration test")
    config.addinivalue_line(
        "markers",
        "multiprocess: spawns a subprocess with a forced multi-device host "
        "platform (XLA_FLAGS=--xla_force_host_platform_device_count)",
    )


# ---------------------------------------------------------------------------
# Bound live compiled-executable volume across the suite.
#
# The full suite JIT-compiles hundreds of distinct programs in one process
# (every arch x policy x batch-shape cell).  XLA:CPU keeps every compiled
# executable mapped for the life of the process, and once a few GB of JIT
# code have accumulated, *later* large compilations (the whisper encoder
# scan is the canary) can segfault inside backend_compile — the crash
# depends only on how much was compiled before, never on which tests ran
# (the same test passes standalone).  Dropping JAX's executable caches
# periodically keeps the process under that ceiling at the cost of a few
# recompiles.
#
# RSS never shrinks back to baseline after a clear (malloc holds pages), so
# a fixed threshold would fire on every test once crossed; instead clear
# whenever RSS has GROWN by _CLEAR_DELTA since the last clear — growth
# since the last clear approximates newly-cached executables.
_CLEAR_DELTA_KB = int(
    os.environ.get("REPRO_TEST_CLEAR_CACHES_DELTA_KB", 3 * 1024 * 1024)
)
_last_clear_rss = [0]


def _rss_kb():
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS"):
                    return int(line.split()[1])
    except OSError:  # non-Linux: no /proc — feature off
        pass
    return 0


@pytest.fixture(autouse=True)
def _bounded_jit_cache():
    yield
    rss = _rss_kb()
    if not rss or _CLEAR_DELTA_KB <= 0:
        return
    if _last_clear_rss[0] == 0:
        _last_clear_rss[0] = rss
        return
    if rss - _last_clear_rss[0] > _CLEAR_DELTA_KB:
        import jax

        jax.clear_caches()
        _last_clear_rss[0] = _rss_kb()


@pytest.fixture(autouse=True)
def _isolated_autotune_cache(tmp_path, monkeypatch):
    """Keep tests hermetic: never read/write the user's on-disk tuned-block
    cache.  Block choice cannot change numerics (the kernel's fixed-order
    reduction is tiling-invariant) — this only isolates *which* tiling
    runs, and the cache files tests create."""
    import sys

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))
    # a previous test may have PINNED the process cache (reset_cache(path));
    # unpin so this test's env isolation takes effect
    mod = sys.modules.get("repro.kernels.autotune")
    if mod is not None:
        mod.reset_cache(None)


@pytest.fixture(autouse=True)
def _restore_compile_cache_dir():
    """Entry points (launch/train.py main) point JAX's persistent
    compilation cache at the checkout; put back what the test found so
    later tests in the worker do not write there."""
    import jax

    before = jax.config.jax_compilation_cache_dir
    yield
    if jax.config.jax_compilation_cache_dir != before:
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
