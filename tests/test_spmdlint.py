"""spmdlint static pass: per-rule fixtures, waivers, budgets, and the
tree-wide zero-unwaived invariant (the CI spmdlint job's contract)."""
import os

import pytest

from tools.spmdlint import RULES
from tools.spmdlint.engine import lint_paths, lint_source
from tools.spmdlint.selftest import FIXTURES, WAIVER_FIXTURE, run_self_test
from tools.spmdlint.waivers import Config, Waiver, load_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- rule fixtures ---------------------------------------------------------

@pytest.mark.parametrize(
    "rule,should_flag,source",
    FIXTURES,
    ids=[f"{r}-{'pos' if f else 'neg'}" for r, f, _ in FIXTURES])
def test_rule_fixture(rule, should_flag, source):
    diags = [d for d in lint_source("<fixture>", source)
             if d.rule == rule]
    if should_flag:
        assert diags, f"{rule} positive fixture produced no finding"
    else:
        assert not diags, [d.format() for d in diags]


def test_fixtures_cover_every_rule_both_ways():
    for rule in RULES:
        kinds = {flag for r, flag, _ in FIXTURES if r == rule}
        assert kinds == {True, False}, (
            f"{rule} needs one positive and one negative fixture")


def test_self_test_passes():
    assert run_self_test(verbose=False) == 0


# -- waivers ---------------------------------------------------------------

def test_waiver_suppresses_matching_finding():
    config = Config(waivers=[Waiver(rule="SPMD001", path="x.py",
                                    symbol="build.local", reason="test")])
    diags = lint_source("x.py", WAIVER_FIXTURE, config)
    assert diags and all(d.waived_by for d in diags)


def test_waiver_does_not_suppress_other_rule_or_path():
    for waiver in (Waiver(rule="SPMD002", path="x.py"),
                   Waiver(rule="SPMD001", path="other.py"),
                   Waiver(rule="SPMD001", path="x.py", symbol="elsewhere")):
        diags = lint_source("x.py", WAIVER_FIXTURE,
                            Config(waivers=[waiver]))
        assert any(d.waived_by is None for d in diags), waiver


def test_waiver_path_matches_by_suffix():
    config = Config(waivers=[Waiver(rule="SPMD001", path="pkg/x.py")])
    diags = lint_source("/abs/prefix/pkg/x.py", WAIVER_FIXTURE, config)
    assert diags and all(d.waived_by for d in diags)


def test_mini_toml_loader(tmp_path):
    toml = tmp_path / "spmdlint.toml"
    toml.write_text(
        '# comment\n'
        '[spmd]\n'
        'axes = ["shard", "row"]\n'
        '\n'
        '[[waiver]]\n'
        'rule = "SPMD001"\n'
        'path = "a/b.py"\n'
        'symbol = "f"\n'
        'reason = "because"\n'
        '[[waiver]]\n'
        'rule = "KER001"\n'
        'path = "c.py"\n')
    config = load_config(str(toml))
    assert config.axes == frozenset({"shard", "row"})
    assert len(config.waivers) == 2
    assert config.waivers[0] == Waiver(rule="SPMD001", path="a/b.py",
                                       symbol="f", reason="because")
    assert config.waivers[1].symbol is None


def test_axes_override_feeds_spmd002(tmp_path):
    src = 'import jax\n\ndef f(x):\n    return jax.lax.psum(x, "row")\n'
    assert any(d.rule == "SPMD002" for d in lint_source("f.py", src))
    config = Config(waivers=[], axes=frozenset({"row"}))
    assert not [d for d in lint_source("f.py", src, config)
                if d.rule == "SPMD002"]


def test_missing_waiver_file_is_empty_config(tmp_path):
    config = load_config(str(tmp_path / "absent.toml"))
    assert config.waivers == [] and config.axes is None


# -- psum budgets ----------------------------------------------------------

def test_budget_counts_through_local_helpers():
    src = (
        "import jax\n\n"
        "def local(x, axis):  # spmdlint: psum-budget=4\n"
        "    def scatter_psum(v):\n"
        "        return jax.lax.psum(v, axis)\n"
        "    a = scatter_psum(x)\n"
        "    b = scatter_psum(x * 2)\n"
        "    c = scatter_psum(x * 3)\n"
        "    return a + b + c + jax.lax.psum(x, axis)\n")
    assert not [d for d in lint_source("f.py", src) if d.rule == "SPMD003"]
    wrong = src.replace("psum-budget=4", "psum-budget=3")
    [d] = [d for d in lint_source("f.py", wrong) if d.rule == "SPMD003"]
    assert "declared 3, counted 4" in d.message


def test_budget_directives_present_in_sharded_kernels():
    """The documented 4-psums/round budgets stay pinned in the source."""
    for rel in ("src/repro/eval/sharded.py", "src/repro/partition/refine.py"):
        with open(os.path.join(REPO, rel), encoding="utf-8") as fh:
            assert "spmdlint: psum-budget=4" in fh.read(), rel


# -- the tree-wide invariant ----------------------------------------------

def test_repo_tree_has_zero_unwaived_findings():
    config = load_config(os.path.join(REPO, "spmdlint.toml"))
    paths = [os.path.join(REPO, p)
             for p in ("src", "tests", "benchmarks", "tools")]
    active = [d for d in lint_paths(paths, config) if d.waived_by is None]
    assert not active, "\n".join(d.format() for d in active)


def test_waivers_all_still_match_something():
    config = load_config(os.path.join(REPO, "spmdlint.toml"))
    # the file was loaded: it declares the hierarchical mesh's axes
    assert config.axes is not None and {"coarse", "refine"} <= config.axes
    diags = lint_paths([os.path.join(REPO, "src")], config)
    for waiver in config.waivers:
        assert any(waiver.matches(d) for d in diags), (
            f"stale waiver: {waiver}")
