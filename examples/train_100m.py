"""End-to-end driver: train a ~100M-parameter dense LM for a few hundred
steps on CPU, with the DLS data scheduler, checkpointing, and a mid-run
injected failure + restart.

Run:  PYTHONPATH=src python examples/train_100m.py [--steps 200]
(~100M params; a few minutes on CPU.)
"""

import argparse

from repro.launch.train import train
from repro.models.config import ModelConfig


def config_100m() -> ModelConfig:
    # ~125M params (untied 32k embeddings: 49M of them): 12L, d=768, llama-style
    return ModelConfig(
        name="repro-100m",
        family="dense",
        n_layers=12,
        d_model=768,
        n_heads=12,
        n_kv_heads=4,
        d_ff=2048,
        vocab=32_000,
        period_pattern=("attn",),
        ffn_pattern=("dense",),
        param_dtype="float32",
        compute_dtype="float32",
    )


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--fail-at", default="120", help="injected failure steps")
    args = ap.parse_args()

    cfg = config_100m()
    print(f"{cfg.name}: {cfg.param_count()/1e6:.1f}M params")
    fail_at = tuple(int(s) for s in args.fail_at.split(",") if s)
    train(
        cfg,
        steps=args.steps,
        batch=args.batch,
        seq=args.seq,
        ckpt_dir="/tmp/repro_100m_ckpt",
        ckpt_every=50,
        technique="fac",
        fail_at=fail_at,
        peak_lr=3e-4,
        log_every=20,
    )
