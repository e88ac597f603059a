"""End-to-end training driver: train a ~100M-param qwen-family model for a
few hundred steps with the full production stack — deterministic data
pipeline, AdamW, atomic checkpoints, auto-resume, straggler watchdog.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 300]
(~100M params needs a few GB RAM; --tiny runs the smoke config.)
"""
import argparse
import dataclasses
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax

from repro.configs import get_arch
from repro.launch.cache import enable_compile_cache
from repro.launch.train import train_loop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()
    enable_compile_cache()

    if args.tiny:
        arch, smoke, gb, seq = "qwen2.5-3b", True, 8, 64
    else:
        # ~100M: qwen2.5 family geometry scaled down (12L x 512d x 8H)
        base = get_arch("qwen2.5-3b")
        cfg100m = dataclasses.replace(
            base, name="qwen2.5-100m", num_layers=12, d_model=512,
            num_heads=8, num_kv_heads=2, head_dim=64, d_ff=2048,
            vocab_size=32768, param_dtype="float32", compute_dtype="float32",
        )
        print(f"training {cfg100m.name}: {cfg100m.param_count()/1e6:.1f}M params")
        # register it so train_loop can resolve it by name
        from repro.configs import ARCHS

        ARCHS[cfg100m.name] = cfg100m
        arch, smoke, gb, seq = cfg100m.name, False, 8, 256

    state, losses, wd = train_loop(
        arch, smoke=smoke, steps=args.steps, global_batch=gb, seq_len=seq,
        ckpt_dir=args.ckpt_dir, ckpt_every=50, log_every=10, lr=3e-4,
    )
    print(f"\nfinal loss: {losses[-1]:.4f} (from {losses[0]:.4f}); "
          f"stragglers flagged: {len(wd.flagged)}")


if __name__ == "__main__":
    main()
