"""End-to-end training driver: data pipeline → pjit train step →
checkpointing → straggler monitor, on a yi-family model.

Default (CPU-sized): ~10M params, 120 steps — finishes in minutes and
demonstrates loss descent + checkpoint/restart. ``--full-100m`` scales to
~100M params / 300 steps for a real machine (same code path).

Run: PYTHONPATH=src python examples/train_lm.py [--full-100m] [--resume]
"""
import argparse
import dataclasses
import sys

from repro.configs import get_config, reduced
from repro.launch import train as train_mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full-100m", action="store_true")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--steps", type=int, default=None)
    args = ap.parse_args()

    if args.full_100m:
        # ~100M params: 8 layers × d512 × ff2048, 32k vocab
        argv = ["--arch", "yi-9b", "--reduced",
                "--steps", str(args.steps or 300),
                "--batch", "16", "--seq", "256",
                "--ckpt-dir", "/tmp/repro_ckpt_100m"]
        # widen the reduced config: the launcher calls the reduced()
        # it imported, so that is the name to override
        base = reduced(get_config("yi-9b"))
        big = dataclasses.replace(base, n_layers=8, d_model=512, d_head=64,
                                  n_heads=8, n_kv_heads=4, d_ff=2048,
                                  vocab_size=32768)
        train_mod.reduced = lambda _cfg, _big=big: _big
    else:
        argv = ["--arch", "yi-9b", "--reduced",
                "--steps", str(args.steps or 120),
                "--batch", "8", "--seq", "64",
                "--ckpt-dir", "/tmp/repro_ckpt_quick"]
    if args.resume:
        argv.append("--resume")
    train_mod.main(argv)


if __name__ == "__main__":
    main()
