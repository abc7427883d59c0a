"""End-to-end resilient training: the full production loop in miniature.

SPTLB routes 48 streaming jobs onto a 5-slice cluster; a reduced assigned
architecture trains on the deterministic token stream with periodic atomic
checkpoints; a mid-run host failure triggers (1) SPTLB re-balancing with the
paper's movement bound, (2) restart from the latest checkpoint.  Exactly the
`launch/train.py` driver — this wrapper picks demonstration-friendly flags.

Run:  PYTHONPATH=src python examples/train_e2e.py [--arch qwen2.5-3b]
"""
import argparse
import tempfile

from repro.launch.train import main as train_main


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--steps", type=int, default=30)
    args = ap.parse_args()

    with tempfile.TemporaryDirectory() as tmp:
        train_main([
            "--arch", args.arch, "--smoke",
            "--steps", str(args.steps),
            "--global-batch", "8",
            "--seq-len", "128",
            "--ckpt-dir", f"{tmp}/ckpt",
            "--ckpt-every", "8",
            "--inject-failure-at", str(args.steps // 2),
        ])


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
