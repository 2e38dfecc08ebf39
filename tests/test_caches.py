"""The shared disk-cache root contract (utils/caches.py): one precedence
rule for the route, stream-layout, and aligned-layout caches."""

import os

from photon_tpu.utils.caches import resolve_cache_dir


def test_explicit_override_wins(monkeypatch):
    monkeypatch.setenv("PHOTON_LAYOUT_CACHE", "/tmp/somewhere")
    monkeypatch.setenv("PHOTON_ROUTE_CACHE", "/tmp/elsewhere")
    assert resolve_cache_dir("PHOTON_LAYOUT_CACHE", "layouts") == "/tmp/somewhere"


def test_zero_disables(monkeypatch):
    monkeypatch.setenv("PHOTON_LAYOUT_CACHE", "0")
    monkeypatch.setenv("PHOTON_ROUTE_CACHE", "/tmp/elsewhere")
    assert resolve_cache_dir("PHOTON_LAYOUT_CACHE", "layouts") is None


def test_follows_route_cache(monkeypatch):
    monkeypatch.delenv("PHOTON_LAYOUT_CACHE", raising=False)
    monkeypatch.setenv("PHOTON_ROUTE_CACHE", "/tmp/routes")
    assert resolve_cache_dir("PHOTON_LAYOUT_CACHE", "layouts") == os.path.join(
        "/tmp/routes", "layouts"
    )


def test_route_zero_disables_followers(monkeypatch):
    monkeypatch.delenv("PHOTON_STREAM_LAYOUT_CACHE", raising=False)
    monkeypatch.setenv("PHOTON_ROUTE_CACHE", "0")
    assert resolve_cache_dir("PHOTON_STREAM_LAYOUT_CACHE", "stream") is None


def test_route_cache_resolves_own_root(monkeypatch):
    monkeypatch.setenv("PHOTON_ROUTE_CACHE", "/tmp/routes")
    assert resolve_cache_dir("PHOTON_ROUTE_CACHE", "") == "/tmp/routes"
    monkeypatch.delenv("PHOTON_ROUTE_CACHE", raising=False)
    root = resolve_cache_dir("PHOTON_ROUTE_CACHE", "")
    assert root is not None  # default root


def test_override_wins_even_when_route_cache_disabled(monkeypatch):
    """Precedence order regression guard: a follower's explicit override
    must win even with PHOTON_ROUTE_CACHE=0 (the suite's own global
    default) — checking the route sentinel first would wrongly disable
    an explicitly enabled cache."""
    monkeypatch.setenv("PHOTON_ROUTE_CACHE", "0")
    monkeypatch.setenv("PHOTON_LAYOUT_CACHE", "/tmp/explicit")
    assert resolve_cache_dir("PHOTON_LAYOUT_CACHE", "layouts") == "/tmp/explicit"


def test_default_root_is_anchored_to_the_checkout(monkeypatch, tmp_path):
    """The default root is resolved from the package location — not the
    working directory, not $HOME — so processes started from different
    directories share one cache."""
    from photon_tpu.utils import caches

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.delenv("PHOTON_ROUTE_CACHE", raising=False)
    monkeypatch.setenv("HOME", str(tmp_path / "home"))
    os.makedirs(tmp_path / ".photon_route_cache")
    monkeypatch.chdir(tmp_path)
    assert caches.resolve_cache_dir("PHOTON_ROUTE_CACHE", "") == os.path.join(
        repo, ".photon_route_cache"
    )


# -- the one compile-cache contract (utils/compilation_cache) ----------------


def _run_py(code, cwd, env_extra, drop=()):
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_extra)
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def test_compile_cache_env_var_wins(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, enable() names that directory
    and sets no other in code."""
    import jax

    from photon_tpu.utils import compilation_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "mine"))
    assert compilation_cache.enable() == str(tmp_path / "mine")
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(tmp_path):
    """Unset, the cache is <repo>/.jax_cache — the same from two processes
    started in different working directories (never $HOME, $TMPDIR, a pid,
    a time or a digest), exported so children inherit it.  And the test
    harness itself honours an externally set directory (conftest)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        f"import sys, os; sys.path.insert(0, {repo!r}); import jax; "
        "from photon_tpu.utils.compilation_cache import enable; "
        "d = enable(); "
        "print(d, jax.config.jax_compilation_cache_dir, "
        "os.environ['JAX_COMPILATION_CACHE_DIR'])"
    )
    other = tmp_path / "elsewhere"
    other.mkdir()
    drop = ("JAX_COMPILATION_CACHE_DIR",)
    expected = " ".join([os.path.join(repo, ".jax_cache")] * 3)
    assert _run_py(code, repo, {"HOME": str(tmp_path)}, drop) == expected
    assert _run_py(
        code, str(other), {"TMPDIR": str(tmp_path)}, drop
    ) == expected

    conftest_code = (
        f"import sys, os; sys.path.insert(0, {os.path.join(repo, 'tests')!r}); "
        "import conftest, jax; "
        "print(os.environ['JAX_COMPILATION_CACHE_DIR'], "
        "jax.config.jax_compilation_cache_dir)"
    )
    mine = str(tmp_path / "external")
    assert _run_py(
        conftest_code, str(other), {"JAX_COMPILATION_CACHE_DIR": mine}
    ) == f"{mine} {mine}"
