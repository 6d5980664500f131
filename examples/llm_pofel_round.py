"""PoFEL at LLM scale (reduced configs on CPU): the in-graph consensus
trainer from repro.fl.pofel_trainer runs real rounds — per-cluster FedSGD
on divergent replicas, Eq. 1/Eq. 2 consensus, BTSV leader election, and a
host-side ledger — for any assigned architecture.

Run:  PYTHONPATH=src python examples/llm_pofel_round.py [--arch rwkv6-1.6b]
"""

import argparse

from repro import compile_cache
from repro.launch.train import train_reduced


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--outer", default="nesterov", choices=["sgd1", "nesterov"])
    args = ap.parse_args()
    compile_cache.enable()
    train_reduced(args.arch, steps=args.steps, n_clusters=4, batch=8,
                  seq=64, seed=0, outer=args.outer)


if __name__ == "__main__":
    main()
