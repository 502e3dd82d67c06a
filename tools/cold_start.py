"""Cold-start probe: process-start → first answer, one phase per run.

Measures what a restart actually costs — in a FRESH process, because
compilation caches only matter across process lifetimes:

- ``--phase serving``: load an exported package, stand up the bucketed
  scheduler (AOT warmup of the whole bucket ladder), answer one
  inference;
- ``--phase train``: build + initialize the MNIST-FC fused workflow and
  complete one train step (the first step pays the fused-step compile).

With ``--cache-dir`` the persistent executable cache
(veles_tpu.compilecache) is enabled; run the same command twice against
the same directory and the second run deserializes instead of
compiling — ``compiles`` drops to 0 and ``warmup_s`` / ``first_step_s``
collapse to deserialization time.  Without it, exactly the seed
behavior.

Emits ONE JSON line:
    {"phase": ..., "import_s": ..., "build_s": ..., "warmup_s": ...,
     "first_infer_s"|"first_step_s": ..., "total_s": ...,
     "compiles": N, "cache_hits": N, "cache": {...} | null}

Run it twice per mode on one cache directory for the cold/warm ratio;
``tests/test_compilecache.py`` uses it as the cross-process reuse proof.
"""

import argparse
import json
import os
import sys
import time

_T0 = time.perf_counter()   # as close to process start as a module gets

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _cache_stats():
    from veles_tpu.compilecache import default_cache
    cache = default_cache()
    if cache is None:
        return None, 0, 0
    stats = cache.stats()
    return stats, stats["hits"], stats["misses"]


def probe_serving(package, max_batch):
    from veles_tpu.export.loader import PackageLoader
    from veles_tpu.serving import BucketScheduler
    import numpy
    t0 = time.perf_counter()
    loader = PackageLoader(package)
    sample_shape = tuple(loader.model_metadata["input"]["sample_shape"])
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    scheduler = BucketScheduler(loader, max_batch=max_batch,
                                name="cold_start")
    warmup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = scheduler.infer(
        numpy.zeros((1,) + sample_shape, numpy.float32))
    first_infer_s = time.perf_counter() - t0
    stats = scheduler.stats()
    scheduler.close()
    return {"build_s": build_s, "warmup_s": warmup_s,
            "first_infer_s": first_infer_s,
            "compiles": stats["compiles"],
            "cache_hits": stats["cache_hits"],
            "buckets": stats["buckets"],
            "bucket_config": stats["bucket_config"],
            "output_rows": int(numpy.asarray(out).shape[0])}


def probe_train(batch=32):
    from veles_tpu import loader as loader_mod, prng
    from veles_tpu.backends import Device
    from veles_tpu.prng import RandomGenerator
    from veles_tpu.znicz.samples import mnist
    import jax
    prng.get().seed(7)
    t0 = time.perf_counter()
    wf = mnist.create_workflow(
        loader={"minibatch_size": batch, "n_train": 4 * batch,
                "n_valid": batch, "use_fixture": False,
                "prng": RandomGenerator().seed(3), "prefetch_depth": 0},
        decision={"max_epochs": 10 ** 9, "silent": True})
    wf.initialize(device=Device(backend="auto"))
    build_s = time.perf_counter() - t0
    step = wf.fused_step
    t0 = time.perf_counter()
    done = 0
    while not done:
        wf.loader.run()
        if wf.loader.minibatch_class == loader_mod.TRAIN:
            step.run()
            done = 1
    jax.block_until_ready(step._params_)
    first_step_s = time.perf_counter() - t0
    return {"build_s": build_s, "warmup_s": 0.0,
            "first_step_s": first_step_s,
            "loss": float(step.loss)}


def main(argv=None):
    p = argparse.ArgumentParser(
        prog="cold_start",
        description="Time process-start -> first inference / train "
                    "step, cache-cold vs cache-warm (run twice).")
    p.add_argument("--phase", choices=("serving", "train"),
                   default="serving")
    p.add_argument("--cache-dir", default=None,
                   help="enable the persistent executable cache here "
                        "(default: off — seed behavior)")
    p.add_argument("--autotune-dir", default=None,
                   help="resolve kernel/serving configs through this "
                        "tuning store (default: off — hand-picked "
                        "defaults)")
    p.add_argument("--package", default=None,
                   help="exported package zip for --phase serving "
                        "(default: build an initialized MNIST package)")
    p.add_argument("--max-batch", type=int, default=16)
    args = p.parse_args(argv)

    t0 = time.perf_counter()
    from veles_tpu.config import root  # noqa: F401 — timed jax import
    import_s = time.perf_counter() - t0 + (t0 - _T0)
    if args.cache_dir:
        root.common.compile_cache.dir = args.cache_dir
    if args.autotune_dir:
        root.common.autotune.dir = args.autotune_dir

    if args.phase == "serving":
        package = args.package
        if package is None:
            import tempfile
            from tools.serve_bench import build_mnist_package
            package = build_mnist_package(os.path.join(
                tempfile.mkdtemp(prefix="cold_start_"), "mnist_pkg.zip"))
        out = probe_serving(package, args.max_batch)
    else:
        out = probe_train()

    cache_stats, hits, misses = _cache_stats()
    out.update({
        "phase": args.phase,
        "import_s": round(import_s, 3),
        "total_s": round(time.perf_counter() - _T0, 3),
        "cache": cache_stats,
        "cache_process_hits": hits,
        "cache_process_misses": misses,
    })
    out = {k: (round(v, 4) if isinstance(v, float) else v)
           for k, v in out.items()}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
