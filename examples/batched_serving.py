"""Batched serving through the ServingEngine: mixed prompt lengths, EOS,
and nucleus sampling (reduced config on CPU; the same decode_step lowers
for decode_32k / long_500k on the production mesh).

Run:  PYTHONPATH=src python examples/batched_serving.py [--arch yi-6b]
"""

import argparse
import time

import jax
import numpy as np

from repro import compile_cache
from repro.configs import ARCH_IDS, get_config
from repro.models.model_api import Model
from repro.serving import GenerationRequest, SamplerConfig, ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b",
                    choices=[a for a in ARCH_IDS if a != "mnist-mlp"])
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--top-p", type=float, default=0.9)
    args = ap.parse_args()
    compile_cache.enable()

    model = Model(get_config(args.arch).reduced())
    params = model.init(jax.random.key(0))
    engine = ServingEngine(
        model, params,
        SamplerConfig(temperature=args.temperature, top_p=args.top_p))

    rng = np.random.default_rng(0)
    requests = [
        GenerationRequest(0, rng.integers(0, 500, 5).astype(np.int32),
                          max_new_tokens=12),
        GenerationRequest(1, rng.integers(0, 500, 17).astype(np.int32),
                          max_new_tokens=8),
        GenerationRequest(2, rng.integers(0, 500, 9).astype(np.int32),
                          max_new_tokens=12, eos_token=7),
    ]
    t0 = time.perf_counter()
    completions = engine.generate(requests)
    dt = time.perf_counter() - t0

    total = sum(len(c.tokens) for c in completions)
    print(f"arch={args.arch} (reduced) — {len(requests)} requests, "
          f"{total} tokens in {dt*1e3:.0f} ms")
    for c in completions:
        print(f"  req{c.request_id} [{c.finished_by:6s}]: {c.tokens}")


if __name__ == "__main__":
    main()
