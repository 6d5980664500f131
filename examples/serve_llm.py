"""Batched serving example: prefill + autoregressive decode with KV caches
(reduced config). Exercises the same decode_step lowered by the decode_32k
and long_500k dry-run shapes.

Run:  PYTHONPATH=src python examples/serve_llm.py [--arch zamba2-7b]
"""

import argparse

from repro import compile_cache
from repro.launch.serve import serve_reduced


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="zamba2-7b")
    ap.add_argument("--batch", type=int, default=4)
    args = ap.parse_args()
    compile_cache.enable()
    serve_reduced(args.arch, batch=args.batch, prompt_len=24, gen=12,
                  seed=0, temperature=0.0)


if __name__ == "__main__":
    main()
