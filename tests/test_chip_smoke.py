"""chip_smoke.py: its contract off the chip, and its phases on the CPU.

On the chip the script runs the main path at n = 2^22. Here it must refuse
to report success (no TPU, or no repository next to it), and its phases
must run end to end at a small n with the jnp backend, so the script does
not rot between chip runs. The Pallas-resolution check is the one part
that only holds on a TPU; these tests stub it."""
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _says_ok(stdout):
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return json.loads(lines[-1]).get("ok") is True
    except ValueError:
        return False


def test_no_tpu_fails_without_ok_line():
    out = _run(SCRIPT, REPO)
    assert out.returncode != 0
    assert not _says_ok(out.stdout)
    assert "no TPU found" in out.stderr


def test_alone_without_repository_fails(tmp_path):
    alone = shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    out = _run(str(alone), str(tmp_path))
    assert out.returncode != 0
    assert not _says_ok(out.stdout)


@pytest.fixture
def smoke(monkeypatch, capsys):
    import chip_smoke
    monkeypatch.setattr(chip_smoke, "check_compiled_pallas",
                        lambda **kw: "jnp (cpu)")
    monkeypatch.setattr(chip_smoke, "flat_memory_analysis",
                        lambda prob: {})

    def phases():
        return [json.loads(line) for line in
                capsys.readouterr().out.strip().splitlines()]
    return chip_smoke, phases


def test_one_chip_phases_run_on_cpu(smoke):
    chip_smoke, phases = smoke
    chip_smoke.one_chip(n=1 << 14)
    got = {p["phase"]: p for p in phases()}
    assert {"flat", "flat_again", "hierarchical", "repartition",
            "assign"} <= set(got)
    for name in ("flat", "hierarchical", "repartition"):
        assert got[name]["imbalance"] <= chip_smoke.EPS
    assert got["assign"]["label_agreement"] >= chip_smoke.LABEL_AGREEMENT


def test_four_chip_phase_runs_on_virtual_devices(smoke):
    chip_smoke, phases = smoke
    chip_smoke.four_chips(n=1 << 14)
    got = phases()
    assert got[0]["phase"] == "placement"
    assert len(set(got[0]["shard_devices"])) == 4
    agree = [p for p in got if p["phase"] == "agreement"]
    assert [p.get("bit_identical") for p in agree][-1] is True
