"""Long-context proof on one chip: train-shaped flash attention at or
beyond the XLA oracle's HBM limit — the oracle materializes [B, H, T, T]
f32 scores (T=16384, H=8: 8 GiB, doubled by its softmax residuals;
T=32768: 32 GiB, over HBM on scores alone), while the flash kernel's
footprint is O(T * D) + O(block) VMEM.

Prints one JSON line per T with achieved tokens/sec and attention
TFLOP/s (4*B*H*T^2*D fwd-causal-halved x3 for train, the standard
convention).

Usage: python tools/longcontext_demo.py [T ...]   (default 16384 32768)
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy  # noqa: E402

import jax.numpy as jnp  # noqa: E402

from tools.ab_flash_attention import train_shaped  # noqa: E402
from veles_tpu.znicz.flash_attention import flash_attention  # noqa: E402

H, D = 8, 64


def run(t, reps=5):
    rng = numpy.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.standard_normal((1, t, H, D)) * 0.5,
                           jnp.float32) for _ in range(3))
    # train_shaped returns a scalar consuming all three grads: the
    # full backward runs (no DCE — the x3 TFLOP accounting needs it)
    # and the flush pulls 4 bytes, not an O(T*D) tensor to the host
    # (both failure modes were review catches here)
    step = train_shaped(
        lambda q, k, v: flash_attention(q, k, v, True), chain=1)
    float(step(q, k, v))  # compile + flush
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        float(step(q, k, v))
        times.append(time.perf_counter() - t0)
    best = min(times)
    # causal ~halves the score FLOPs; x3 for fwd+bwd
    flops = 3 * (4 * H * t * t * D / 2)
    return {"T": t, "heads": H, "head_dim": D,
            "train_step_s": round(best, 4),
            "tokens_per_sec": round(t / best, 1),
            "attn_tflops_per_sec": round(flops / best / 1e12, 2),
            "oracle_scores_gib": round(H * t * t * 4 / 2 ** 30, 1)}


if __name__ == "__main__":
    ts = [int(a) for a in sys.argv[1:]] or [16384, 32768]
    for t in ts:
        print(json.dumps(run(t)), flush=True)
