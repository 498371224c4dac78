"""Serving scenario: batched request serving of a small LM.

Trains nothing — initializes a smoke-scale gemma3-style model, admits a
wave of variable-length requests through the batched ServeEngine (static
slots, per-row EOS masking), and reports tokens/sec and per-request
transcripts. The same ServeEngine drives the decode_32k / long_500k
dry-run cells at production scale.

    PYTHONPATH=src python examples/serve_lm.py [--arch rwkv6_3b]
"""
from repro.envflags import use_compile_cache

use_compile_cache()          # before the first jax import

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.dist.rules import resolve_rules
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.serve import Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3_1b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--n-requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    args = ap.parse_args()

    cfg = configs.get_config(args.arch, smoke=True)
    mesh = make_host_mesh()
    rules = resolve_rules(mesh, cfg, "decode")
    params = M.init_params(cfg, jax.random.PRNGKey(0))

    rng = np.random.default_rng(1)
    shape = lambda n: ((n,) if cfg.input_mode == "tokens"
                       else (n, cfg.n_codebooks))
    reqs = [Request(uid=i,
                    prompt=rng.integers(
                        0, cfg.vocab_size,
                        shape(int(rng.integers(4, 12)))).astype(np.int32),
                    max_new=args.max_new)
            for i in range(args.n_requests)]

    engine = ServeEngine(cfg, rules, params, batch=args.batch, max_seq=64)
    t0 = time.perf_counter()
    engine.run(reqs)
    dt = time.perf_counter() - t0
    total = sum(len(r.out) for r in reqs)
    for r in reqs:
        print(f"req {r.uid}: prompt_len={len(r.prompt):2d} -> {r.out}")
    print(f"\n{len(reqs)} requests / {total} new tokens in {dt:.2f}s "
          f"({total/dt:.1f} tok/s interpret-mode host loop)")


if __name__ == "__main__":
    main()
