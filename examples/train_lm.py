"""End-to-end training driver: a ~100M-parameter llama3.2-style model
trained for a few hundred steps on CPU, with the raw-array cached data
pipeline, sharded params over the host mesh, AdamW, and async checkpoints.

  PYTHONPATH=src python examples/train_lm.py [--steps 200]
"""
import argparse
import sys
import tempfile


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--arch", default="llama3.2-3b")
    args = ap.parse_args()

    from repro.launch.train import main as train_main
    # ~100M params: d_model=512, 14 periods of the llama pattern, vocab 32k
    # (vocab dominates: 2 x 32000 x 512 = 33M; blocks ~ 55M).
    out = train_main([
        "--arch", args.arch,
        "--scale", "reduced",
        "--d-model", "512",
        "--periods", "14",
        "--steps", str(args.steps),
        "--batch", "8",
        "--seq", "128",
        "--vocab", "32000",
        "--lr", "3e-4",
        "--ckpt-dir", tempfile.mkdtemp(prefix="ckpt_"),
        "--ckpt-every", "50",
        "--log-every", "10",
    ])
    losses = out["losses"]
    print(f"\nfirst-10 mean loss {sum(losses[:10])/10:.3f} -> "
          f"last-10 mean loss {sum(losses[-10:])/10:.3f}")
    assert losses[-1] < losses[0], "training should reduce the loss"


if __name__ == "__main__":
    main()
