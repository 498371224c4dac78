"""envflags.force_virtual_devices — the pre-jax-import entry point.

Every harness (tests/conftest.py, benchmarks/run.py, the examples) calls
this before the first jax import; its contract is pure environment-string
surgery, so it is testable without touching jax at all."""
import os

import pytest

from repro.envflags import _COUNT_FLAG, force_virtual_devices


@pytest.fixture
def xla_flags(monkeypatch):
    """Sandbox XLA_FLAGS; returns a reader for its current value."""
    def read():
        return os.environ.get("XLA_FLAGS", "")
    return read


def test_sets_flag_when_unset(monkeypatch, xla_flags):
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    force_virtual_devices(8)
    assert xla_flags() == f"{_COUNT_FLAG}=8"


def test_appends_to_existing_operator_flags(monkeypatch, xla_flags):
    monkeypatch.setenv("XLA_FLAGS", "--xla_cpu_enable_fast_math=false")
    force_virtual_devices(4)
    assert xla_flags() == (
        f"--xla_cpu_enable_fast_math=false {_COUNT_FLAG}=4")


def test_existing_count_flag_wins_without_override(monkeypatch, xla_flags):
    operator = f"{_COUNT_FLAG}=2 --xla_dump_to=/tmp/x"
    monkeypatch.setenv("XLA_FLAGS", operator)
    force_virtual_devices(8)
    assert xla_flags() == operator          # exact no-op


def test_override_replaces_only_the_count_flag(monkeypatch, xla_flags):
    monkeypatch.setenv(
        "XLA_FLAGS",
        f"--xla_dump_to=/tmp/x {_COUNT_FLAG}=2 --xla_cpu_use_thunks=true")
    force_virtual_devices(16, override=True)
    flags = xla_flags().split()
    # the other operator flags survive, in order, exactly once
    assert flags[:2] == ["--xla_dump_to=/tmp/x", "--xla_cpu_use_thunks=true"]
    assert flags[2:] == [f"{_COUNT_FLAG}=16"]


def test_repeated_calls_are_idempotent(monkeypatch, xla_flags):
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    force_virtual_devices(8)
    first = xla_flags()
    force_virtual_devices(8)
    force_virtual_devices(4)                 # existing flag wins
    assert xla_flags() == first


def test_override_from_unset_is_clean(monkeypatch, xla_flags):
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    force_virtual_devices(3, override=True)
    assert xla_flags() == f"{_COUNT_FLAG}=3"


# ---------------------------------------------------------------------------
# use_compile_cache — the persistent compilation cache directory
# ---------------------------------------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_defaults_to_checkout(monkeypatch):
    from repro.envflags import use_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert use_compile_cache() == want
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == want


def test_compile_cache_dir_set_outside_wins(monkeypatch, tmp_path):
    from repro.envflags import use_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)


_PROBE = """
from repro.envflags import use_compile_cache
use_compile_cache()
import jax, jax.numpy as jnp
print(jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: jnp.sin(x) * 2.0)(jnp.ones(8)).block_until_ready()
"""


@pytest.mark.parametrize("outside", [False, True], ids=["unset", "set"])
def test_jax_uses_the_cache_dir(tmp_path, outside):
    """In a fresh process jax reads the directory ``use_compile_cache``
    chose; with the variable set from outside the compiled program lands
    there."""
    import subprocess
    import sys
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    want = os.path.join(REPO, ".jax_cache")
    if outside:
        want = str(tmp_path / "cache")
        env["JAX_COMPILATION_CACHE_DIR"] = want
        code = _PROBE
    else:
        code = _PROBE.split("jax.jit")[0]     # no write into the checkout
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == want
    if outside:
        assert os.listdir(want)
