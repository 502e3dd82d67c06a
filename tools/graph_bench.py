"""Interpreted vs traced throughput probes for whole-workflow compilation.

Three probes, each printing ONE JSON line (run each in a fresh process;
``tests/test_graphcomp.py`` does):

- ``nonstd``: a deliberately NON-standard workflow — two-branch forward
  towers joined into a shared softmax head + evaluator (an ensemble-style
  eval loop no ``FusedTrainStep`` can express) — measured interpreted then
  traced on the SAME process, with the traced run's ``n_err`` asserted
  equal to the interpreted run's (parity rides into the bench record);
- ``std``: the standard MNIST-FC training topology three ways — graph-mode
  interpreted, graph-mode traced, and the hand-fused step — to prove the
  tracer gives the per-unit graph fused-step speed and that the blessed
  fused path does not regress under the knob;
- ``warm``: build + run the nonstd workflow traced against ``--cache-dir``
  and report the compile cache's stats() — the driver runs it twice in
  fresh subprocesses; the second run proving ``compiles == 0`` is the
  zero-recompile warm-restart evidence.

Throughput is measured over the LAST ``--epochs`` epochs via per-epoch
wall-clock stamps (an epoch-boundary probe unit), excluding the leading
warmup epochs that contain all compilation.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy  # noqa: E402


def _fresh_prng(seed):
    from veles_tpu.prng import RandomGenerator
    import veles_tpu.prng.random_generator as rg
    rg._generators.clear()
    rg.get(0).seed(seed)
    return RandomGenerator().seed(seed + 1)


class EpochClock:
    """Per-epoch wall-clock stamps via a host probe unit; ips over the
    last N epochs (compile-free steady state)."""

    def __init__(self, workflow):
        from veles_tpu.units import Unit

        class _Probe(Unit):
            hide_from_registry = True

            def __init__(self, wf, clock):
                super().__init__(wf, name="EpochClock")
                self.clock = clock
                self.epoch_ended = None

            def run(self):
                if bool(self.epoch_ended):
                    self.clock.stamps.append(time.perf_counter())
        self.stamps = []
        probe = _Probe(workflow, self)
        probe.link_attrs(workflow.loader, "epoch_ended")
        probe.link_from(workflow.decision)
        self.start = time.perf_counter()

    def ips(self, samples_per_epoch, last):
        """min-of-epochs estimator over the LAST ``last`` epochs: each
        epoch is identical deterministic work, so the fastest one is the
        quiet-window throughput (the same contention-cancelling trick
        the other bench stages use)."""
        stamps = [self.start] + self.stamps
        durations = [b - a for a, b in zip(stamps, stamps[1:])][-last:]
        if not durations or min(durations) <= 0:
            return None
        return samples_per_epoch / min(durations)


def build_two_branch(n_train=4096, n_valid=512, minibatch=128, hidden=48,
                     n_features=24, n_classes=6, max_epochs=6, seed=31,
                     branches=2, graph_compile=False):
    """Multi-branch forward + shared evaluator: loader fans out into
    independent 2-layer towers whose outputs concatenate (InputJoiner)
    into a softmax head scored by EvaluatorSoftmax — an eval-loop DAG
    outside ``FusedTrainStep``'s chain shape."""
    from veles_tpu.backends import Device
    from veles_tpu.input_joiner import InputJoiner
    from veles_tpu.loader.base import TEST, VALID, TRAIN
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.plumbing import Repeater
    from veles_tpu.workflow import Workflow
    from veles_tpu.znicz.all2all import All2AllTanh, All2AllSoftmax
    from veles_tpu.znicz.decision import TrivialDecision
    from veles_tpu.znicz.evaluator import EvaluatorSoftmax

    prng = _fresh_prng(seed)

    class _Blobs(FullBatchLoader):
        hide_from_registry = True

        def load_data(self):
            rng = numpy.random.RandomState(7)
            total = n_train + n_valid
            centers = rng.uniform(-2, 2, (n_classes, n_features))
            labels = rng.randint(0, n_classes, total)
            data = centers[labels] + 0.6 * rng.standard_normal(
                (total, n_features))
            self.original_data.mem = data.astype(numpy.float32)
            self.original_labels = list(labels)
            self.class_lengths[TEST] = 0
            self.class_lengths[VALID] = n_valid
            self.class_lengths[TRAIN] = n_train

    wf = Workflow(None, name="two_branch")
    repeater = Repeater(wf)
    loader = _Blobs(wf, minibatch_size=minibatch, prng=prng)
    wf.loader = loader
    repeater.link_from(wf.start_point)
    loader.link_from(repeater)

    towers = []
    for b in range(branches):
        up = All2AllTanh(wf, output_sample_shape=hidden,
                         name="tower%d_up" % b)
        up.link_from(loader)
        up.link_attrs(loader, ("input", "minibatch_data"))
        down = All2AllTanh(wf, output_sample_shape=hidden // 2,
                           name="tower%d_down" % b)
        down.link_from(up)
        down.link_attrs(up, ("input", "output"))
        towers.append(down)
    joiner = InputJoiner(wf)
    joiner.link_from(*towers)
    joiner.link_inputs(*[(t, "output") for t in towers])
    head = All2AllSoftmax(wf, output_sample_shape=n_classes, name="Head")
    head.link_from(joiner)
    head.link_attrs(joiner, ("input", "output"))
    evaluator = EvaluatorSoftmax(wf)
    evaluator.link_from(head)
    evaluator.link_attrs(head, "output", "max_idx")
    evaluator.link_attrs(loader, ("labels", "minibatch_labels"),
                         ("batch_size", "minibatch_size"))
    decision = TrivialDecision(wf, max_epochs=max_epochs)
    decision.link_from(evaluator)
    decision.link_loader(loader)
    wf.decision = decision
    repeater.link_from(decision)
    wf.end_point.link_from(decision)
    repeater.gate_block = decision.complete
    wf.end_point.gate_block = ~decision.complete
    wf.initialize(device=Device(backend="auto"))
    if graph_compile:
        wf.attach_graph_compiler()
    return wf


def probe_nonstd(epochs=6, warmup=2, repeats=2, **kwargs):
    out = {}
    n_err = {}
    ips = {"interpreted": 0.0, "traced": 0.0}
    # interleave whole runs (i, t, i, t, ...) and keep each mode's best
    # min-epoch estimate: slow drift on a contended host cancels out
    for _rep in range(repeats):
        for mode in ("interpreted", "traced"):
            wf = build_two_branch(max_epochs=warmup + epochs,
                                  graph_compile=(mode == "traced"),
                                  **kwargs)
            clock = EpochClock(wf)
            wf.run()
            ips[mode] = max(ips[mode],
                            clock.ips(wf.loader.total_samples, epochs)
                            or 0.0)
            n_err[mode] = int(wf["EvaluatorSoftmax"].n_err[0])
            if mode == "traced":
                stats = wf.graph_controller.stats()
                out["graph_nonstd_regions"] = stats["regions"]
                out["graph_nonstd_traced_units"] = stats["traced_units"]
                out["graph_nonstd_variants"] = stats["variants"]
    for mode, value in ips.items():
        out["graph_nonstd_%s_ips" % mode] = round(value, 1)
    if ips["traced"] and ips["interpreted"]:
        out["graph_nonstd_speedup"] = round(
            ips["traced"] / ips["interpreted"], 3)
    out["graph_nonstd_bitwise_n_err"] = \
        n_err["interpreted"] == n_err["traced"]
    return out


def _build_mnist(mode, minibatch, n_train, n_valid, max_epochs):
    from veles_tpu.backends import Device
    from veles_tpu.znicz.samples import mnist
    _fresh_prng(11)
    from veles_tpu.prng import RandomGenerator
    wf = mnist.create_workflow(
        fused=(mode == "fused"),
        graph_compile=(mode == "traced"),
        loader={"minibatch_size": minibatch, "n_train": n_train,
                "n_valid": n_valid, "use_fixture": False,
                "prng": RandomGenerator().seed(3)},
        decision={"max_epochs": max_epochs, "silent": True})
    wf.initialize(device=Device(backend="auto"))
    return wf


def probe_std(epochs=6, warmup=2, minibatch=512, n_train=8192,
              n_valid=512, repeats=2):
    out = {}
    ips = {}
    for _rep in range(repeats):   # interleaved, best-of (see nonstd)
        for mode in ("interpreted", "traced", "fused"):
            wf = _build_mnist(mode, minibatch, n_train, n_valid,
                              warmup + epochs)
            clock = EpochClock(wf)
            wf.run()
            ips[mode] = max(ips.get(mode, 0.0),
                            clock.ips(wf.loader.total_samples, epochs)
                            or 0.0)
    for mode, value in ips.items():
        out["graph_std_%s_ips" % mode] = round(value, 1)
    t, i, f = (out["graph_std_traced_ips"],
               out["graph_std_interpreted_ips"], out["graph_std_fused_ips"])
    if t and i:
        out["graph_std_traced_vs_interpreted"] = round(t / i, 3)
    if t and f:
        out["graph_std_traced_vs_fused"] = round(t / f, 3)
    return out


def probe_warm(cache_dir, epochs=2):
    from veles_tpu.config import root
    root.common.compile_cache.dir = cache_dir
    from veles_tpu.compilecache import reset_default_caches
    reset_default_caches()
    wf = build_two_branch(max_epochs=epochs, graph_compile=True)
    wf.run()
    controller = wf.graph_controller
    from veles_tpu.compilecache import default_cache
    stats = default_cache().stats()
    return {"graph_compiles": stats["misses"],
            "graph_cache_hits": stats["hits"],
            "graph_variants": controller.stats()["variants"],
            "graph_controller_compiles":
                controller.stats()["compiles"]}


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--probe", required=True,
                        choices=("nonstd", "std", "warm"))
    parser.add_argument("--epochs", type=int, default=6)
    parser.add_argument("--warmup", type=int, default=2)
    parser.add_argument("--cache-dir", default=None)
    args = parser.parse_args(argv)
    if args.probe == "nonstd":
        out = probe_nonstd(epochs=args.epochs, warmup=args.warmup)
    elif args.probe == "std":
        out = probe_std(epochs=args.epochs, warmup=args.warmup)
    else:
        if not args.cache_dir:
            parser.error("--probe warm requires --cache-dir")
        out = probe_warm(args.cache_dir, epochs=max(args.epochs, 2))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
