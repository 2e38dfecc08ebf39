"""Test configuration: force an 8-device CPU platform.

Mirrors the reference's test strategy (SURVEY.md §4): the reference tests
"distributed" code paths with local-mode Spark in one JVM; we test sharded
code paths with 8 virtual CPU devices in one process
(``--xla_force_host_platform_device_count=8``).  Must run before jax import.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Tests run on the host, explicitly: the device policy
# (drivers/common.select_backend) treats JAX_PLATFORMS=cpu as the request
# for CPU, and subprocesses spawned by tests inherit it.
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)

# Persistent compilation cache: the suite is dominated by XLA compiles of
# optimizer while_loops and GAME programs that are identical run-to-run.
# One contract (utils/compilation_cache): an externally set
# JAX_COMPILATION_CACHE_DIR is honoured as is.  Otherwise the suite keeps
# its own repo-local (gitignored) cache, KEYED by jaxlib version + a digest
# of the photon_tpu sources: stale cached programs from an older repo
# revision once segfaulted runs when a donated-buffer program's aliasing
# metadata no longer matched the cache entry loaded for it.  A source or
# jaxlib change lands in a FRESH cache subdirectory (stale siblings are
# pruned) — so the first suite run after any photon_tpu edit is cold.


def _repo_state_digest() -> str:
    import hashlib

    root = os.path.abspath(
        os.path.join(os.path.dirname(__file__), os.pardir, "photon_tpu")
    )
    h = hashlib.sha256()
    h.update(jax.__version__.encode())
    try:
        import jaxlib

        h.update(jaxlib.__version__.encode())
    except Exception:
        pass
    for dirpath, dirnames, filenames in sorted(os.walk(root)):
        dirnames.sort()
        for name in sorted(filenames):
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            h.update(os.path.relpath(path, root).encode())
            with open(path, "rb") as f:
                h.update(f.read())
    return h.hexdigest()[:12]


if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _cache_root = os.path.abspath(
        os.path.join(os.path.dirname(__file__), os.pardir, ".jax_test_cache")
    )
    _cache_key = _repo_state_digest()
    # Prune stale entries (old keyed subdirs AND pre-keying flat cache
    # files) so the workspace cache never grows one dead copy per source
    # change — and a stale program can never be picked up again.
    if os.path.isdir(_cache_root):
        import shutil

        for entry in os.listdir(_cache_root):
            if entry != _cache_key:
                full = os.path.join(_cache_root, entry)
                try:
                    shutil.rmtree(full) if os.path.isdir(full) else os.remove(full)
                except OSError:
                    pass
    # Through the environment, so worker SUBPROCESSES spawned by tests (the
    # multi-process suite, bench.py) share the cache; this process, which
    # imported jax above, takes it from the config update below.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        _cache_root, _cache_key
    )
# Threshold 0: the suite compiles hundreds of SMALL programs (0.05-0.2s
# each) across ~220 tests; caching them all is worth far more than the
# cache-dir inode count it costs.
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0.0"
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
jax.config.update(
    "jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"]
)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

# Pin the feature-major gradient kernel: correctness tests must exercise the
# production fm path even on platforms where the runtime autotuner
# (ops/sparse_grad_select) would prefer the autodiff scatter; the selection
# logic itself is tested explicitly with env overrides.
os.environ.setdefault("PHOTON_SPARSE_GRAD", "fm")

# The host layout caches (under this root) must NOT serve tests: a stale
# cached layout would mask builder regressions.  The caches themselves are
# covered by dedicated tests with an explicit tmp-dir override.
os.environ.setdefault("PHOTON_ROUTE_CACHE", "0")

# Hermetic fixtures: an operator's ambient PHOTON_REAL_DATA_DIR would
# silently redirect the a1a/MovieLens anchor tests to real data, whose
# metrics fall outside the fixture-calibrated bands.  Tests that cover the
# hook set the variable themselves via monkeypatch.
os.environ.pop("PHOTON_REAL_DATA_DIR", None)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 run (`-m 'not slow'`); full CLI "
        "subprocess drives and other minute-scale checks",
    )


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Bound the CPU client's accumulated compiled-executable state.

    A single-shot full-suite run compiles hundreds of XLA programs into one
    process; past ~200 tests the CPU backend segfaults inside a fresh
    compile (observed twice, deterministically, at the same test — any
    subset of the suite passes).  Dropping the in-memory executable caches
    at module boundaries keeps the client small; re-runs of shared programs
    reload from the persistent disk cache configured above, so the time
    cost is deserialization, not recompilation.
    """
    yield
    jax.clear_caches()
