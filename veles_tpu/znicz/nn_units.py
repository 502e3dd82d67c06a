"""Forward / gradient-descent base units for the NN layer library.

Re-creation of the absent ``veles.znicz.nn_units`` (ForwardBase /
GradientDescentBase — SURVEY.md §2.9; solver/regularization knobs per
/root/reference/docs/source/manualrst_veles_algorithms.rst:150-165).

TPU-first contract: every Forward implements

- ``init_params()`` — allocate weights/bias host-side with the unit's
  reproducible :class:`RandomGenerator` (reference replays RandomState per
  unit, units.py:859-885);
- ``apply(params, x)`` — a *pure* function of ``params = {"weights": W,
  "bias": b}`` usable under jit/grad/vmap/shard_map.  Graph-mode ``run``
  wraps it; the StandardWorkflow fused step composes the whole chain of
  ``apply``s into one jitted train step with ``jax.value_and_grad``.

Every GradientDescent unit implements explicit backward math (``numpy_run``
twin + jitted kernel) so graph mode matches the fused autodiff path — that
equivalence is asserted by the tests.
"""

import numpy

from ..accelerated_units import AcceleratedUnit
from ..memory import Array
from .. import backends, prng
from . import solvers


import threading as _threading

_oracle_only_state = _threading.local()


class oracle_only:
    """Context manager forcing every Pallas-capable unit onto its pure
    XLA/jnp formulation while tracing (regardless of knobs).  Used by
    the exporter: a Mosaic ``tpu_custom_call`` baked into a StableHLO
    artifact would break the package's any-backend portability
    contract (export/loader.py).  Thread-LOCAL: an export on one
    thread must not flip concurrent traces (e.g. a training retrace)
    on other threads onto the slower oracle path."""

    def __enter__(self):
        _oracle_only_state.depth = getattr(
            _oracle_only_state, "depth", 0) + 1
        return self

    def __exit__(self, *exc):
        _oracle_only_state.depth -= 1
        return False


def resolve_use_pallas(setting, device, tpu_auto):
    """Shared tri-state ``use_pallas`` semantics for every
    Pallas-capable unit: True/False force the choice; None (unset) =
    AUTO — the per-unit measured best, which is ``tpu_auto`` when the
    unit's device is the TPU and False elsewhere (CPU interpret-mode
    kernels are orders slower; docs/PERF.md carries the per-kernel
    measurements: flash attention wins on TPU, the LRN pair loses).
    Inside :class:`oracle_only` everything resolves False."""
    if getattr(_oracle_only_state, "depth", 0):
        return False
    if setting is not None:
        return bool(setting)
    if not tpu_auto:
        return False
    backend = getattr(device, "BACKEND", None)
    if backend is None:  # unit not initialized (direct apply/trace)
        return backends.on_tpu()
    return backend == "tpu"


class NNUnitBase(AcceleratedUnit):
    hide_from_registry = True

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        self.prng = kwargs.get("prng", prng.get())


class ForwardBase(NNUnitBase):
    """Base for forward propagation units (weights + bias + activation)."""

    hide_from_registry = True
    view_group = "WORKER"
    MAPPING = None  # StandardWorkflow layer-type key
    #: True for units whose train-time forward draws randomness (dropout,
    #: stochastic pooling) — they implement apply_train(params, x, key);
    #: the key ARRIVES AS AN ARGUMENT so jit never freezes the draw
    stochastic = False

    def export_params(self):
        """Structural hyperparameters for the package archive — what the
        native engine needs to rebuild this unit (reference libVeles
        Unit::SetParameter from contents.json, unit.h:87-92)."""
        return {}

    def apply_train(self, params, x, key=None):
        """Train-time forward; defaults to the eval forward.  Stochastic
        units override and consume ``key``."""
        return self.apply(params, x)

    #: stochastic units hold a KeyTree; graph mode draws one key per train
    #: minibatch and records it so the matching backward can regenerate
    #: the same draw (no mask storage needed)
    key_tree = None
    minibatch_class = None   # linked from the loader for stochastic units

    def _graph_training(self):
        from .. import loader as loader_mod
        return self.stochastic and \
            self.minibatch_class == loader_mod.TRAIN

    def step_key(self):
        self._last_key_ = self.key_tree.key_for(self.name)
        return self._last_key_

    @property
    def last_key(self):
        return getattr(self, "_last_key_", None)

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        self.input = None               # linked from the previous unit
        self.output = Array()
        self.weights = Array()
        self.bias = Array()
        self.include_bias = bool(kwargs.get("include_bias", True))
        self.weights_stddev = kwargs.get("weights_stddev")
        self.bias_stddev = kwargs.get("bias_stddev",
                                      kwargs.get("weights_stddev"))
        self.weights_filling = kwargs.get("weights_filling", "uniform")
        self.bias_filling = kwargs.get("bias_filling", "uniform")
        # include_bias is structural config (export_params), not a tensor
        self.exports = ["weights", "bias"]

    # -- parameter handling --------------------------------------------------
    @property
    def params(self):
        """The layer's trainable pytree (device views)."""
        p = {}
        if self.weights:
            p["weights"] = self.weights.devmem
        if self.include_bias and self.bias:
            p["bias"] = self.bias.devmem
        return p

    def set_params(self, params):
        """Accept fresh device values from the fused step."""
        if "weights" in params:
            self.weights.devmem = params["weights"]
        if "bias" in params:
            self.bias.devmem = params["bias"]

    @property
    def host_params(self):
        """Host (numpy) twin of :attr:`params` — the numpy backend and
        the GD host path read through this, so units with extra
        parameter tensors (attention's ``proj``) override params/
        host_params as a pair."""
        p = {}
        if self.weights:
            p["weights"] = self.weights.map_read()
        if self.include_bias and self.bias:
            p["bias"] = self.bias.map_read()
        return p

    def set_host_params(self, params):
        if "weights" in params:
            self.weights.mem = numpy.asarray(params["weights"],
                                             numpy.float32)
        if "bias" in params:
            self.bias.mem = numpy.asarray(params["bias"], numpy.float32)

    def fill_array(self, arr, shape, stddev, filling):
        n_in = shape[0] if len(shape) > 1 else max(shape[0], 1)
        if stddev is None:
            stddev = 1.0 / numpy.sqrt(n_in)
        mem = numpy.zeros(shape, numpy.float32)
        if filling == "uniform":
            self.prng.fill(mem, -stddev, stddev)
        elif filling == "gaussian":
            mem[...] = self.prng.normal(0, stddev, shape)
        elif filling == "constant":
            mem[...] = stddev
        else:
            raise ValueError("unknown filling %r" % filling)
        arr.mem = mem

    def init_params(self):
        raise NotImplementedError

    def apply(self, params, x):
        raise NotImplementedError

    # -- graph-mode execution ------------------------------------------------
    def output_shape_for(self, input_shape):
        """Shape of the output for a given input shape; lets initialize
        pre-allocate ``output`` so downstream units can size themselves
        before the first run (reference forwards allocate in initialize)."""
        raise NotImplementedError

    #: methods every concrete forward must implement (verified at
    #: initialize — reference verified.py contract role)
    CONTRACT = ("apply", "output_shape_for")

    def initialize(self, device=None, **kwargs):
        from ..verified import verify_contract
        verify_contract(self, ForwardBase)
        super().initialize(device=device, **kwargs)
        if not self.weights:
            self.init_params()
        out_shape = self.output_shape_for(self.input_shape)
        if not self.output or tuple(self.output.shape) != tuple(out_shape):
            self.output.reset(numpy.zeros(out_shape, numpy.float32))

    @property
    def input_shape(self):
        v = self.input
        return v.shape if isinstance(v, Array) else numpy.shape(v)

    def tpu_init(self):
        import jax
        self._jitted_ = jax.jit(self.apply)
        if self.stochastic:
            self._jitted_train_ = jax.jit(self.apply_train)

    def make_trace(self):
        """Generic forward face: ``apply(params, x)`` is already the pure
        function graph-compilation needs; the params ride the region's
        donated carry (shared, by key, with the GD unit that updates
        them).  Stochastic forwards draw per-minibatch keys host-side and
        stay interpreted."""
        from ..graphcomp.faces import (NoFace, TraceFace,
                                       forward_params_leaf)
        if self.stochastic:
            return NoFace("stochastic forward (host-side per-minibatch "
                          "key draws)")
        if type(self).tpu_run is not ForwardBase.tpu_run:
            return NoFace("custom tpu_run (side effects beyond the pure "
                          "apply)")
        if not self._initialized:
            return NoFace("unit not initialized")
        if getattr(self, "_backend_run_", None) != self.tpu_run:
            return NoFace("numpy backend (no jitted path)")
        state = (forward_params_leaf(self),) if self.params else ()

        def fn(state_in, inputs, statics):
            return {}, {"output": self.apply(state_in.get("params", {}),
                                             inputs["input"])}
        return TraceFace(self, fn, inputs=("input",), outputs=("output",),
                         state=state, sync_attrs=("weights", "bias"))

    def tpu_run(self):
        x = self.input.devmem if isinstance(self.input, Array) else self.input
        if self._graph_training():
            self.output.devmem = self._jitted_train_(
                self.params, x, self.step_key())
        else:
            self.output.devmem = self._jitted_(self.params, x)

    def numpy_run(self):
        x = self.input.map_read() if isinstance(self.input, Array) \
            else numpy.asarray(self.input)
        params = self.host_params
        if self._graph_training():
            # replay the device draw exactly on host (jnp on CPU)
            self.output.mem = numpy.asarray(
                self.apply_train(params, x, self.step_key()))
        else:
            self.output.mem = numpy.asarray(self.apply_numpy(params, x))

    def apply_numpy(self, params, x):
        """Host twin; default falls back to the jnp apply (exact on CPU)."""
        return self.apply(params, x)


class ParamlessForward(ForwardBase):
    """Base for forwards with no trainable parameters (pooling, dropout,
    activations, structural units)."""

    hide_from_registry = True

    def init_params(self):
        pass

    @property
    def params(self):
        return {}

    def set_params(self, params):
        pass

    def output_shape_for(self, input_shape):
        return tuple(input_shape)


class GradientDescentBase(NNUnitBase):
    """Base for backward/update units.

    Linked attributes (reference GD contract): ``input`` (forward's input),
    ``output`` (forward's output), ``err_output`` (gradient flowing in from
    the next layer or the evaluator); produces ``err_input`` and updates the
    forward's ``weights``/``bias`` in place through a two-way link.
    """

    hide_from_registry = True
    view_group = "TRAINER"
    MAPPING = None

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        self.input = None
        self.output = None
        self.err_output = None
        self.batch_size = None     # linked: loader.minibatch_size (valid
        #                            rows; padded rows carry zero err)
        self.err_input = Array()
        self.weights = None        # linked two-way with the forward
        self.bias = None
        self.forward_unit = None   # set by link_forward / StandardWorkflow
        self.learning_rate = kwargs.get("learning_rate", 0.01)
        self.learning_rate_bias = kwargs.get("learning_rate_bias",
                                             kwargs.get("learning_rate",
                                                        0.01))
        self.weights_decay = kwargs.get("weights_decay", 0.0)
        self.weights_decay_bias = kwargs.get("weights_decay_bias", 0.0)
        self.l1_vs_l2 = kwargs.get("l1_vs_l2", 0.0)
        self.l1_vs_l2_bias = kwargs.get("l1_vs_l2_bias",
                                        kwargs.get("l1_vs_l2", 0.0))
        self.factor_ortho = kwargs.get("factor_ortho", 0.0)
        self.gradient_moment = kwargs.get("gradient_moment", 0.0)
        self.solver_name = kwargs.get(
            "solver", "momentum" if self.gradient_moment else "sgd")
        hyper = dict(kwargs.get("solver_parameters", {}))
        if self.solver_name == "momentum":
            hyper.setdefault("momentum", self.gradient_moment or 0.9)
        self.solver = solvers.factory(self.solver_name, **hyper)
        self.solver_state = {}     # param name -> state tuple
        self.need_err_input = bool(kwargs.get("need_err_input", True))
        self.batch_normalize_grad = False

    def link_forward(self, fwd):
        """Wire the standard attribute set to a forward unit."""
        self.forward_unit = fwd
        self.link_attrs(fwd, "input", "output", two_way=False)
        self.link_attrs(fwd, "weights", "bias", two_way=True)
        return self

    # -- solver plumbing -----------------------------------------------------
    def ensure_solver_state(self, params, xp=numpy):
        for name, p in params.items():
            if name not in self.solver_state:
                self.solver_state[name] = self.solver.init(p, xp)

    def lr_for(self, name):
        return self.learning_rate_bias if name == "bias" \
            else self.learning_rate

    def decay_for(self, name):
        if name == "bias":
            return self.weights_decay_bias, self.l1_vs_l2_bias, 0.0
        return self.weights_decay, self.l1_vs_l2, self.factor_ortho

    def apply_updates(self, params, grads, xp=numpy):
        """Pure-ish solver application; returns new params dict and stores
        new solver state."""
        self.ensure_solver_state(params, xp)
        out = {}
        for name, p in params.items():
            g = grads[name]
            decay, l1l2, ortho = self.decay_for(name)
            g = solvers.regularized_grad(g, p, decay, l1l2, xp, ortho)
            delta, new_state = self.solver.update(
                g, p, self.solver_state[name], self.lr_for(name), xp)
            self.solver_state[name] = new_state
            out[name] = p + delta
        return out

    # -- backward interface --------------------------------------------------
    def backward(self, params, x, y, err_output, n_valid=None):
        """Pure backward: returns (err_input, grads dict).  Gradients are
        the mean over the *valid* rows (padded rows carry zero error)."""
        raise NotImplementedError

    def backward_via_vjp(self, params, x, err_output, n_valid):
        """Generic backward through jax.vjp of the forward's pure apply —
        the exact chain rule the fused path uses, so graph mode and fused
        mode agree by construction.  Units with hand-written backward math
        (the all2all family) override ``backward`` directly; structured ops
        (conv, pooling, LRN) use this."""
        import jax
        fwd = self.forward_unit
        _, pullback = jax.vjp(lambda p, xx: fwd.apply(p, xx), params, x)
        grads, err_input = pullback(err_output)
        grads = jax.tree.map(lambda g: g / n_valid, grads)
        return err_input, grads

    def _n_valid(self, x):
        return int(self.batch_size) if self.batch_size is not None \
            else x.shape[0]

    def _gather_params(self, host):
        """The forward's FULL param dict (overridable shapes like
        attention's ``proj`` included); hardcoded weights/bias only when
        no forward is linked (hand-built test graphs)."""
        fwd = self.forward_unit
        if fwd is not None:
            return dict(fwd.host_params if host else fwd.params)
        if host:
            params = {"weights": self._host(self.weights)}
            if self.bias:
                params["bias"] = self._host(self.bias)
            return params
        params = {"weights": self.weights.devmem}
        if self.bias:
            params["bias"] = self.bias.devmem
        return params

    def _store_params(self, new_params, host):
        fwd = self.forward_unit
        if fwd is not None:
            (fwd.set_host_params if host else fwd.set_params)(new_params)
            return
        if host:
            self.weights.mem = numpy.asarray(new_params["weights"],
                                             numpy.float32)
            if self.bias and "bias" in new_params:
                self.bias.mem = numpy.asarray(new_params["bias"],
                                              numpy.float32)
        else:
            self.weights.devmem = new_params["weights"]
            if self.bias and "bias" in new_params:
                self.bias.devmem = new_params["bias"]

    def numpy_run(self):
        x = self._host(self.input)
        y = self._host(self.output)
        err_out = self._host(self.err_output)
        params = self._gather_params(host=True)
        err_in, grads = self.backward_numpy(params, x, y, err_out,
                                            self._n_valid(x))
        new_params = self.apply_updates(params, grads, numpy)
        self._store_params(new_params, host=True)
        if self.need_err_input:
            self.err_input.mem = numpy.asarray(err_in, numpy.float32)

    def backward_numpy(self, params, x, y, err_output, n_valid=None):
        return self.backward(params, x, y, err_output, n_valid)

    def tpu_init(self):
        import jax
        # n_valid stays static (bounded set of sizes → bounded retraces)
        self._jitted_bwd_ = jax.jit(self.backward, static_argnames="n_valid")
        # backward + regularizer + solver update as ONE jit: one dispatch
        # per GD run instead of jit(backward) plus ~6 eager solver ops
        # per parameter, and — critically — the exact function the graph
        # compiler composes into whole-workflow programs, so traced and
        # interpreted dispatch are bitwise-identical by construction.
        # Learning rates ride as ARGUMENTS (LearningRateAdjuster mutates
        # them per epoch without retracing); decay/solver hyperparameters
        # are closed over and fingerprinted by the face's config key.
        self._jitted_step_ = jax.jit(self._device_step,
                                     static_argnames="n_valid")

    def _device_step(self, params, solver_state, x, y, err_output, lr,
                     lr_bias, n_valid):
        """Pure fused backward: (params', solver_state', err_input)."""
        import jax.numpy as jnp
        err_in, grads = self.backward(params, x, y, err_output,
                                      n_valid=n_valid)
        new_params, new_state = {}, {}
        for name, p in params.items():
            g = grads[name]
            decay, l1l2, ortho = self.decay_for(name)
            g = solvers.regularized_grad(g, p, decay, l1l2, jnp, ortho)
            delta, st = self.solver.update(
                g, p, solver_state[name],
                lr_bias if name == "bias" else lr, jnp)
            new_params[name] = p + delta
            new_state[name] = st
        return new_params, new_state, err_in

    def tpu_run(self):
        import numpy
        import jax.numpy as jnp
        x = self._dev(self.input)
        y = self._dev(self.output)
        err_out = self._dev(self.err_output)
        params = self._gather_params(host=False)
        if getattr(self, "_jitted_step_", None) is None:
            # subclasses overriding tpu_init (dropout, stochastic
            # pooling) keep the classic jit(backward) + eager-update path
            err_in, grads = self._jitted_bwd_(params, x, y, err_out,
                                              n_valid=self._n_valid(x))
            new_params = self.apply_updates(params, grads, jnp)
        else:
            self.ensure_solver_state(params, jnp)
            state = {n: self.solver_state[n] for n in params}
            new_params, new_state, err_in = self._jitted_step_(
                params, state, x, y, err_out,
                numpy.float32(self.learning_rate),
                numpy.float32(self.learning_rate_bias),
                n_valid=self._n_valid(x))
            for n, st in new_state.items():
                self.solver_state[n] = st
        self._store_params(new_params, host=False)
        if self.need_err_input:
            self.err_input.devmem = err_in

    def make_trace(self):
        """Generic GD face: composes :meth:`_device_step` — the SAME
        function the interpreted path jits — into the region program;
        params are shared (by key) with the linked forward, solver state
        is this unit's own carry synced back into ``solver_state``."""
        from ..graphcomp.faces import (NoFace, TraceFace, forward_params_leaf,
                                       gd_params_leaf, solver_state_leaf)
        if type(self).tpu_init is not GradientDescentBase.tpu_init:
            return NoFace("custom backward path (per-minibatch host "
                          "state)")
        if type(self).tpu_run is not GradientDescentBase.tpu_run:
            return NoFace("custom tpu_run")
        if type(self).apply_updates is not GradientDescentBase.apply_updates:
            return NoFace("custom update rule")
        if not self._initialized:
            return NoFace("unit not initialized")
        if getattr(self, "_backend_run_", None) != self.tpu_run:
            return NoFace("numpy backend (no jitted path)")
        fwd = self.forward_unit
        state = []
        if fwd is not None and fwd.params:
            state.append(forward_params_leaf(fwd))
        elif fwd is None and self.weights:
            state.append(gd_params_leaf(self))
        if state:
            params_of = (lambda: dict(fwd.params)) if fwd is not None \
                else (lambda: self._gather_params(host=False))
            state.append(solver_state_leaf(self, params_of))
        outputs = ("err_input",) if self.need_err_input else ()
        config = (self.decay_for("weights"), self.decay_for("bias"),
                  self.solver_name,
                  tuple(sorted(self.solver.hyper.items())),
                  self.need_err_input)

        def fn(state_in, inputs, statics):
            n_valid = statics["batch_size"]
            if n_valid is None:
                n_valid = inputs["input"].shape[0]
            new_p, new_s, err_in = self._device_step(
                state_in.get("params", {}), state_in.get("solver", {}),
                inputs["input"], inputs["output"], inputs["err_output"],
                inputs["learning_rate"], inputs["learning_rate_bias"],
                int(n_valid))
            updates = {"params": new_p, "solver": new_s} if new_p else {}
            outs = {"err_input": err_in} if self.need_err_input else {}
            return updates, outs
        return TraceFace(
            self, fn,
            inputs=("input", "output", "err_output", "learning_rate",
                    "learning_rate_bias"),
            statics=("batch_size",), outputs=outputs, state=tuple(state),
            config=config)

    @staticmethod
    def _host(v):
        if isinstance(v, Array):
            return v.map_read()
        return numpy.asarray(v)

    @staticmethod
    def _dev(v):
        if isinstance(v, Array):
            return v.devmem
        return v


class GenericVJPBackward(GradientDescentBase):
    """Fallback backward for layer types without a registered GD pair
    (structural units: splitters, depooling, ...): pure vjp pass-through
    of the forward, no parameters."""

    hide_from_registry = True

    def __init__(self, workflow, **kwargs):
        kwargs.setdefault("learning_rate", 0.0)
        super().__init__(workflow, **kwargs)

    def backward(self, params, x, y, err_output, n_valid=None):
        if n_valid is None:
            n_valid = x.shape[0]
        err_in, _ = self.backward_via_vjp({}, x, err_output, n_valid)
        return err_in, {}

    def backward_numpy(self, params, x, y, err_output, n_valid=None):
        err_in, grads = self.backward(params, x, y, err_output, n_valid)
        return numpy.asarray(err_in), grads
