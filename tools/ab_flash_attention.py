"""Interleaved on-chip A/B: flash-attention kernel pair vs the XLA
oracle (``attention_reference``), forward-only and train-shaped
(fwd+bwd), at long-context MHA shapes.

Interleaved, not sequential: a one-chip machine shares its host's CPU
cores, and load drift can invert sequential same-process comparisons
(round-4 lesson, docs/PERF.md).  Each repetition times A then B back-to-back;
the reported ratio uses per-pair minima.

Usage:  python tools/ab_flash_attention.py [T ...]
Prints one JSON line per shape.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from veles_tpu.parallel.ring import attention_reference  # noqa: E402
from veles_tpu.znicz.flash_attention import flash_attention  # noqa: E402


def _sync(x):
    return float(numpy.asarray(jax.tree_util.tree_leaves(x)[0]).ravel()[0])


def train_shaped(attend, chain):
    """Jitted full train step xchain: grads wrt ALL THREE operands —
    grad wrt q alone would let XLA dead-code-eliminate an oracle's
    dK/dV matmuls while a flash custom-VJP kernel computes all three
    (asymmetric A/B).  Returns ONE SCALAR that consumes all three
    updates: the last iteration's dK/dV work stays alive (no DCE)
    while the caller's sync pulls 4 bytes — syncing on the updated
    tensors themselves would put an O(T*D) device-to-host copy of
    q'/k'/v' into every rep, an additive constant on both sides that
    dilutes the ratio toward 1.  Shared with tools/longcontext_demo.py."""
    import jax
    import jax.numpy as jnp

    def loss(q, k, v):
        return jnp.sum(attend(q, k, v) ** 2)

    def run(q, k, v):
        for _ in range(chain):
            gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
            q, k, v = q - 1e-3 * gq, k - 1e-3 * gk, v - 1e-3 * gv
        return jnp.sum(q) + jnp.sum(k) + jnp.sum(v)
    return jax.jit(run)


def time_pair(fa, fb, args, reps=12, chain=4):
    """Interleaved A/B timing discipline (round-4 lesson: contention
    drift inverts sequential comparisons): compile+warm both fns, then
    each repetition times A then B back-to-back; ``chain`` dependent
    calls per dispatch amortize host dispatch.  Returns the
    full per-rep second lists (callers take min/median/spread)."""
    for fn in (fa, fb):
        _sync(fn(*args))
    ta, tb = [], []
    for _ in range(reps):
        for fn, acc in ((fa, ta), (fb, tb)):
            t0 = time.perf_counter()
            out = fn(*args)
            _sync(out)
            acc.append((time.perf_counter() - t0) / chain)
    return ta, tb


def ab_shape(b, t, h, d, causal=True, chain=4):
    rng = numpy.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.standard_normal((b, t, h, d)) * 0.5,
                           jnp.float32) for _ in range(3))

    def chained(attend):
        def run(q, k, v):
            out = q
            for _ in range(chain):  # data-dependent: one dispatch
                out = attend(out, k, v)
            # scalar output: the sync must not copy O(T*D) to the
            # host (see train_shaped)
            return jnp.sum(out)
        return jax.jit(run)

    flash = lambda q, k, v: flash_attention(q, k, v, causal)  # noqa: E731
    oracle = lambda q, k, v: attention_reference(  # noqa: E731
        q, k, v, causal=causal)
    res = {"shape": [b, t, h, d], "causal": causal}
    for tag, wrap in (("fwd", chained),
                      ("train", lambda f: train_shaped(f, chain))):
        fa, fb = wrap(flash), wrap(oracle)
        ta, tb = time_pair(fa, fb, (q, k, v), chain=chain)
        a, b_ = min(ta), min(tb)
        res.update({tag + "_flash_s": round(a, 5),
                    tag + "_xla_s": round(b_, 5),
                    tag + "_speedup": round(b_ / a, 3)})
    return res


if __name__ == "__main__":
    ts = [int(a) for a in sys.argv[1:]] or [1024, 2048, 4096]
    for t in ts:
        # B*H scaled down as T grows: keep the oracle's [B,H,T,T]
        # scores in HBM range
        b = max(1, 4096 // t)
        line = ab_shape(b, t, 8, 64)
        print(json.dumps(line), flush=True)
