"""Train a small LM end-to-end on the synthetic Markov pipeline with
checkpointing + restart (wraps the production launcher).

    PYTHONPATH=src python examples/train_lm.py            # ~2 min on CPU
    PYTHONPATH=src python examples/train_lm.py --arch xlstm-350m
"""

import argparse
import sys

from repro.core.runtime import use_compile_cache
from repro.launch.train import main as train_main

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()
    use_compile_cache()
    train_main(["--arch", args.arch, "--smoke", "--steps", str(args.steps),
                "--batch", "8", "--seq", "64", "--lr", "1e-2",
                "--ckpt-dir", args.ckpt_dir, "--ckpt-every", "40"])
