"""``python -m tools.spmdlint --self-test`` — per-rule fixture suite.

Each rule ships one positive fixture it must flag and one negative
fixture it must pass, plus a waiver-suppression check. CI runs this in
the lint job so a rule regression (a detector silently going blind, or
a new false positive) fails the build even before the tree-wide pass.
The same fixtures back tests/test_spmdlint.py.
"""
from __future__ import annotations

from .engine import lint_source
from .waivers import Config, Waiver

# (rule, should_flag, source) — fixture sources are tiny but shaped like
# the real call sites the rule exists for.
FIXTURES: list[tuple[str, bool, str]] = [
    ("SPMD001", True, """
import jax
from jax import shard_map

def build(mesh, spec):
    def local(x):
        return jax.lax.all_gather(x, "shard")
    return shard_map(local, mesh=mesh, in_specs=spec, out_specs=spec)
"""),
    ("SPMD001", False, """
import jax
from jax import shard_map

def build(mesh, spec):
    def local(x):
        return jax.lax.psum(x, "shard")
    return shard_map(local, mesh=mesh, in_specs=spec, out_specs=spec)
"""),
    ("SPMD002", True, """
import jax

def local(x):
    return jax.lax.psum(x, "shards")
"""),
    ("SPMD002", False, """
import jax

def local(x, axis):
    return jax.lax.psum(x, "shard") + jax.lax.pmax(x, axis)
"""),
    ("SPMD003", True, """
import jax

def local(x, axis):  # spmdlint: psum-budget=2
    return jax.lax.psum(x, axis)
"""),
    ("SPMD003", False, """
import jax

def local(x, axis):  # spmdlint: psum-budget=3
    def helper(v):
        return jax.lax.psum(v, axis)
    return helper(x) + helper(x * 2) + jax.lax.psum(x, axis)
"""),
    ("TRC001", True, """
import jax

@jax.jit
def f(x):
    n = int(x)
    return n + 1
"""),
    ("TRC001", False, """
import jax

@jax.jit
def f(x):
    n = int(x.shape[0])      # shape metadata is static under tracing
    try:
        m = int(x)           # guarded concretization (warm-up pattern)
    except jax.errors.TracerIntegerConversionError:
        m = 0
    return n + m
"""),
    ("TRC002", True, """
import functools
import jax
import numpy as np

@functools.partial(jax.jit, static_argnames=("cfg",))
def f(x, cfg):
    return np.sum(x)
"""),
    ("TRC002", False, """
import functools
import jax
import numpy as np

@functools.partial(jax.jit, static_argnames=("cfg",))
def f(x, cfg):
    scale = np.float32(cfg.scale)     # static config -> numpy is fine
    return x * scale
"""),
    ("TRC003", True, """
import jax

def run(xs):
    def body(carry, x):
        if x > 0:
            carry = carry + x
        return carry, x
    return jax.lax.scan(body, 0, xs)
"""),
    ("TRC003", False, """
import functools
import jax
import jax.numpy as jnp

@functools.partial(jax.jit, static_argnames=("cfg",))
def f(x, cfg):
    if cfg.warmup:                      # static argname -> host branch ok
        x = x * 2
    return jnp.where(x > 0, x, 0.0)     # traced select, not Python if
"""),
    ("KER001", True, """
import jax.numpy as jnp
from jax.experimental import pallas as pl

def _kernel(x_ref, o_ref):
    o_ref[...] = jnp.sort(x_ref[...])   # no Mosaic lowering for sort

def run(x, out_shape):
    if x.shape[0] % 8:
        raise ValueError("bad tile")
    return pl.pallas_call(_kernel, out_shape=out_shape)(x)
"""),
    ("KER001", False, """
import jax.numpy as jnp
from jax.experimental import pallas as pl

def _kernel(x_ref, o_ref):
    o_ref[...] = jnp.maximum(x_ref[...], 0.0).astype(o_ref.dtype)

def run(x, out_shape):
    if x.shape[0] % 8:
        raise ValueError("bad tile")
    return pl.pallas_call(_kernel, out_shape=out_shape)(x)
"""),
    ("KER002", True, """
from jax.experimental.pallas import tpu as pltpu

def _kernel(hbm, buf, sem):
    pltpu.make_async_copy(hbm, buf, sem).start()
"""),
    ("KER002", False, """
from jax.experimental.pallas import tpu as pltpu

def _kernel(hbm, buf, sem):
    def dma(slot):
        return pltpu.make_async_copy(hbm, buf.at[slot], sem)
    dma(0).start()
    dma(0).wait()
"""),
    ("KER003", True, """
from jax.experimental import pallas as pl

def _kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]

def run(x, out_shape):
    return pl.pallas_call(_kernel, out_shape=out_shape)(x)
"""),
    ("KER003", False, """
from jax.experimental import pallas as pl

def _check_tiling(n, block):
    if n % block:
        raise ValueError(f"{n} not a multiple of {block}")

def _kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]

def run(x, out_shape, block):
    _check_tiling(x.shape[0], block)
    return pl.pallas_call(_kernel, out_shape=out_shape)(x)
"""),
    ("REG001", True, """
from repro.kernels.ops import register_assign_backend

@register_assign_backend("mine")
def backend(points, centers, influence, **kw):
    return None
"""),
    ("REG001", False, """
from repro.kernels.ops import register_assign_backend
from repro.partition.refine import register_refiner

@register_assign_backend("mine", supports_moments=False)
def backend(points, centers, influence, **kw):
    return None

@register_refiner("noop", aliases=("n",), short="no")
def noop(problem, labels, **kw):
    return labels, {}
"""),
]

#: the positive fixture a waiver must be able to silence
WAIVER_FIXTURE = FIXTURES[0][2]


def run_self_test(verbose: bool = True) -> int:
    failures = []
    for rule, should_flag, source in FIXTURES:
        diags = lint_source(f"<fixture:{rule}>", source)
        hits = [d for d in diags if d.rule == rule and d.waived_by is None]
        others = [d for d in diags if d.rule != rule]
        kind = "positive" if should_flag else "negative"
        if should_flag and not hits:
            failures.append(f"{rule} {kind}: expected a finding, got none")
        elif not should_flag and hits:
            failures.append(
                f"{rule} {kind}: false positive(s): "
                + "; ".join(d.format() for d in hits))
        if others:
            failures.append(
                f"{rule} {kind}: unrelated finding(s) leaked in: "
                + "; ".join(d.format() for d in others))

    config = Config(waivers=[Waiver(
        rule="SPMD001", path="<fixture:waiver>", symbol="build.local",
        reason="self-test")])
    waived = lint_source("<fixture:waiver>", WAIVER_FIXTURE, config)
    if any(d.waived_by is None for d in waived):
        failures.append("waiver suppression: finding survived a matching "
                        "waiver")
    if not any(d.waived_by for d in waived):
        failures.append("waiver suppression: expected a waived finding")

    if verbose:
        n = len(FIXTURES) + 1
        if failures:
            for f in failures:
                print(f"FAIL {f}")
            print(f"spmdlint self-test: {len(failures)} failure(s) / "
                  f"{n} checks")
        else:
            print(f"spmdlint self-test: {n} checks passed")
    return 1 if failures else 0
