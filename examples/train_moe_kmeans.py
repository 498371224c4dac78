"""End-to-end training driver: MoE LM with the paper's balanced-k-means
router, checkpointed + resumable.

Presets:
  cpu-small  (default) — ~8M-param MoE, 300 steps: finishes on this CPU
             container and shows (i) loss well below uniform entropy,
             (ii) the router influence state adapting (paper Eq. 1),
             (iii) dropped-token fraction staying low without aux losses.
  100m       — ~100M-param config (d=512, 12L, 16 experts), the "train a
             ~100M model for a few hundred steps" driver for real
             hardware; identical code path.

    PYTHONPATH=src python examples/train_moe_kmeans.py [--preset 100m]
        [--steps 300] [--ckpt-dir /tmp/moe_ckpt] [--quick]

``--quick`` runs the cpu-small preset for a handful of steps as a smoke
test (exercises the full train loop but skips the learning assertion,
which needs a few hundred steps to hold).
"""
from repro.envflags import use_compile_cache

use_compile_cache()          # before the first jax import

import argparse

import jax
import numpy as np

from repro.data.pipeline import SyntheticLM
from repro.dist.rules import resolve_rules
from repro.launch.mesh import make_host_mesh
from repro.models.config import LayerSpec, ModelConfig, MoEConfig
from repro.train import Trainer, TrainerConfig, TrainHParams

PRESETS = {
    "cpu-small": dict(
        cfg=ModelConfig(
            name="moe-8m",
            n_layers=4, d_model=128, n_heads=4, n_kv_heads=2,
            d_ff=256, vocab_size=2048,
            moe=MoEConfig(n_experts=8, top_k=2, d_ff=256,
                          capacity_factor=1.25, router="balanced_kmeans"),
            pattern=(LayerSpec("full", "dense"), LayerSpec("full", "moe")),
        ),
        batch=8, seq=128, steps=300),
    "100m": dict(
        cfg=ModelConfig(
            name="moe-100m",
            n_layers=12, d_model=512, n_heads=8, n_kv_heads=4,
            d_ff=1408, vocab_size=32_000,
            moe=MoEConfig(n_experts=16, top_k=2, d_ff=1408,
                          capacity_factor=1.25, router="balanced_kmeans"),
            pattern=(LayerSpec("full", "dense"), LayerSpec("full", "moe")),
        ),
        batch=32, seq=1024, steps=300),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=list(PRESETS), default="cpu-small")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--quick", action="store_true",
                    help="smoke run: cpu-small preset, 5 steps, no "
                         "learning assertion")
    args = ap.parse_args()

    if args.quick:
        args.preset = "cpu-small"
    p = PRESETS[args.preset]
    cfg = p["cfg"]
    steps = args.steps or (5 if args.quick else p["steps"])
    print(f"model: {cfg.name}  params={cfg.param_count()/1e6:.1f}M "
          f"(active {cfg.active_param_count()/1e6:.1f}M)")

    mesh = make_host_mesh()
    rules = resolve_rules(mesh, cfg, "train")
    hp = TrainHParams(microbatches=args.microbatches, lr_peak=3e-3,
                      warmup_steps=max(steps // 20, 5), total_steps=steps)
    tc = TrainerConfig(steps=steps, log_every=max(steps // 30, 1),
                       ckpt_every=max(steps // 3, 1) if args.ckpt_dir else 0,
                       ckpt_dir=args.ckpt_dir)
    trainer = Trainer(cfg, rules, hp, tc)
    data = SyntheticLM(cfg, p["batch"], p["seq"])
    state, history = trainer.fit(iter(data))

    uniform = float(np.log(cfg.vocab_size))
    print(f"\n{'step':>6s} {'loss':>8s} {'drop%':>7s} {'gnorm':>8s}")
    for h in history:
        print(f"{int(h['step']):6d} {h['loss']:8.4f} "
              f"{100*h['moe_dropped_frac']:7.2f} {h['grad_norm']:8.2f}")
    final = history[-1]["loss"]
    print(f"\nuniform-entropy baseline: {uniform:.3f}; final loss {final:.3f}")
    infl = np.asarray(jax.device_get(state["influence"]))
    print(f"router influence range after training: "
          f"[{infl.min():.3f}, {infl.max():.3f}] (adapting => != 1.0)")
    if args.quick:
        print("(--quick: skipping learning assertion — needs a few "
              "hundred steps)")
    else:
        assert final < uniform - 0.5, "model failed to learn"


if __name__ == "__main__":
    main()
