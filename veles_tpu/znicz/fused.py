"""FusedTrainStep: the whole forward+loss+backward+update chain as ONE
jitted, donated step function.

This is the TPU-native collapse of the reference's hot loop (SURVEY.md §3.1:
one thread-pool dispatch + gate lock per unit per minibatch).  The unit
graph remains the *build-time* description — forwards and GD configs are
taken from the same units graph mode uses — but at run time a single
``jax.jit`` function with donated params/opt-state executes per minibatch:

    (params, opt, x, labels, size) -> (params', opt', loss, n_err)

Buffer donation keeps one copy of the params in HBM; the loss for softmax
heads uses fused log-softmax cross-entropy on the *logits* (numerically
stabler and one less HBM round-trip than materializing probabilities).
Metrics surface through the same ``n_err``/``metrics`` Arrays the
evaluator exposes, so Decision units work unchanged.
"""

import logging

import numpy

from ..backends import compiles_not_persisted
from ..compilecache import AotStep, default_cache
from ..config import root
from ..logger import events
from ..memory import Array
from ..result_provider import IResultProvider
from ..units import Unit
from .. import loader as loader_mod
from ..parallel import mesh as mesh_mod
from .all2all import All2AllSoftmax
from .evaluator import EvaluatorSoftmax, EvaluatorMSE
from . import solvers


def jit_program(placement, fn, in_kinds, out_kinds, donate_argnums,
                host_argnums=()):
    """One program of a trainer step, jitted once: with no placement
    the plain ``jax.jit`` (no sharding named, no array committed), with
    one (``parallel.mesh.TrainerPlacement``) its SPMD jit, the operands
    laid out by kind."""
    if placement is None:
        import jax
        return jax.jit(fn, donate_argnums=donate_argnums)
    return placement.jit(fn, in_kinds, out_kinds, donate_argnums,
                         host_argnums)


def applier(fwd):
    """What the chain calls of the forward unit ``fwd``: its
    ``apply_stats`` where it counts, else its ``apply``; under
    ``jax.checkpoint`` where the unit declares ``remat``, and that
    checkpoint keeping the values the unit names in ``remat_saves``
    (``jax.ad_checkpoint.checkpoint_name``) where it names any."""
    import jax
    fn = getattr(fwd, "apply_stats", fwd.apply)
    if not getattr(fwd, "remat", False):
        return fn
    saves = getattr(fwd, "remat_saves", ())
    if not saves:
        return jax.checkpoint(fn)
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.save_only_these_names(*saves))


def jaxpr_equations(jaxpr):
    """Every equation of ``jaxpr`` and, depth first, of the jaxprs among
    its equations' parameters (a checkpoint's, a custom rule's, a
    scan's)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)        # a closed one
                if hasattr(sub, "eqns"):
                    yield from jaxpr_equations(sub)


def saved_bytes(fwd, keep_f32, cdtype):
    """The bytes of the values ``fwd``'s ``apply`` names among its
    ``remat_saves``, which the train step holds from the unit's forward
    pass to its backward pass: from the shapes alone, by a trace of the
    unit over one whole minibatch (over a mesh: all shards') in the
    chain's arithmetic.  A path that names nothing (explicit scores in
    place of the kernel) holds nothing."""
    import jax
    import jax.numpy as jnp

    def in_chain(a, cast):
        floating = jnp.issubdtype(a.dtype, jnp.floating)
        return jax.ShapeDtypeStruct(
            tuple(a.shape), cdtype if cast and floating else a.dtype)
    cast = cdtype is not None
    params = {k: in_chain(p, cast and k not in keep_f32)
              for k, p in fwd.params.items()}
    traced = jax.make_jaxpr(fwd.apply)(params, in_chain(fwd.input, cast))
    return sum(
        v.aval.size * v.aval.dtype.itemsize
        for eqn in jaxpr_equations(traced.jaxpr)
        if eqn.primitive.name == "name"
        and eqn.params["name"] in fwd.remat_saves for v in eqn.outvars)


def scope_names(units):
    """The ``jax.named_scope`` of each unit of a chain: the unit's own
    name, with its index where two units share one.  A trace then names
    the device's operations by layer (``conv2``, ``transpose(jvp(conv2))``
    for its backward pass) instead of by instruction and shape."""
    names = [u.name for u in units]
    return [name if names.count(name) == 1 else "%s%d" % (name, i)
            for i, name in enumerate(names)]


class FusedTrainStep(Unit, IResultProvider):
    """One-step fused trainer over a chain of forward units.

    Parameters: ``forwards`` (list of ForwardBase), ``gd_configs`` (list of
    GradientDescentBase *or* kwargs dicts, one per forward, reverse not
    required), ``loss`` ("softmax" | "mse").

    ``mesh`` (a ``jax.sharding.Mesh``) makes the same step one SPMD
    program: the batch split over ``data_axis``, the parameters
    replicated or split over ``model_axis`` (``tp_mode``: "column" |
    "megatron"), XLA inserting the gradient all-reduce from the sharding
    annotations.  That is the TPU-native replacement for the reference's
    master-slave trainer (SURVEY.md §2.4), and synchronous where that
    was stale by one update; its elastic join/leave moved to
    checkpoint-restart (``veles_tpu.distributed``).  Which operand lies
    where is ``parallel.mesh.TrainerPlacement``'s to say; with no mesh
    the step holds no placement and its jits take no shardings.
    """

    #: what one dispatch covers, for ``make_trace``
    DISPATCH = "one compiled donated program per minibatch"
    #: whether ``run()`` dispatches the per-minibatch programs.  Over a
    #: mesh they then take the placement; a class that dispatches
    #: others (the epoch scan) leaves them plain jits, which take a
    #: caller's arrays wherever they lie (the benchmark's correctness
    #: check hands ``_eval_step_`` a replicated batch)
    DISPATCHES_STEPS = True

    def __init__(self, workflow, forwards, gd_units, loss="softmax",
                 mesh=None, data_axis="data", model_axis=None,
                 tp_mode="column", **kwargs):
        super().__init__(workflow, **kwargs)
        self.view_group = "TRAINER"
        self.mesh = mesh
        self.data_axis = data_axis
        self.model_axis = model_axis
        self.tp_mode = tp_mode
        self.gather_loader = None   # set by link_fused_gather
        self.forwards = list(forwards)
        self.gd_units = list(gd_units)
        assert len(self.gd_units) == len(self.forwards)
        self.loss_kind = loss
        # linked from loader:
        self.minibatch_data = None
        self.minibatch_labels = None
        self.minibatch_targets = None
        self.minibatch_size = None
        self.minibatch_class = None
        self.last_minibatch = None
        self.epoch_number = None
        # evaluator-compatible metric surface:
        self.n_err = Array(numpy.zeros(1, numpy.int64))
        self.metrics = Array(numpy.zeros(3, numpy.float64))
        self.metrics.mem[2] = numpy.inf
        self.confusion_matrix = Array()
        self.max_err_output_sum = Array(numpy.zeros(1, numpy.float32))
        # the [C, C] accumulator rides the jitted carry; for large class
        # counts (C^2 ints per device, one_hot + scatter-add per step) turn
        # it off like the graph evaluator's knob (evaluator.py)
        self.compute_confusion_matrix = bool(
            kwargs.get("compute_confusion_matrix", True))
        self.loss = None
        #: {class name: {unit scope: {counter: total}}} of the forward
        #: units that count (``apply_stats``), filed at each class end
        self.unit_stats = {}
        self.output = Array()      # last forward's output (for consumers)
        self.max_idx = Array()
        # deterministic per-step seed for stochastic units (dropout,
        # stochastic pooling); pickles with the snapshot.  Kept within
        # int32 so it passes as a jit scalar without overflow.
        self._seed_counter = (int(kwargs.get("seed", 42)) *
                              1_000_003) % 0x7FFF0000
        # global learning-rate multiplier, set per epoch by
        # LearningRateAdjuster; 1.0 = the configured base rates
        self.lr_scale = 1.0
        # mixed precision: "bfloat16" runs the forward/backward matmuls
        # in bf16 (full MXU rate) while master params, the loss, and the
        # solver update stay f32 — the standard TPU recipe.  None = f32
        # throughout (bit-parity with graph mode).
        self.compute_dtype = kwargs.get(
            "compute_dtype", root.common.engine.get("dtype", "float32"))
        if self.compute_dtype in ("float32", None):
            self.compute_dtype = None

    def link_loader(self, loader):
        self.link_attrs(loader, "minibatch_data", "minibatch_labels",
                        "minibatch_size", "minibatch_class",
                        "last_minibatch")
        if hasattr(loader, "epoch_number"):
            # the unit of work the step's spans are filed under
            self.link_attrs(loader, "epoch_number")
        if hasattr(loader, "minibatch_targets"):
            self.link_attrs(loader, "minibatch_targets")
        return self

    def link_fused_gather(self, loader):
        """Fuse the HBM-resident minibatch gather INTO the jitted step.

        The loader then only computes shuffled indices host-side; the
        ``jnp.take`` rides inside the same executable as the forward/
        backward — one launch per step instead of two, and the gathered
        batch is no buffer between two executables.  That is less HBM
        traffic only where rows CAN be gathered from the set as it lies:
        from the v5e's default layout of ``f32[8704, 227, 227, 3]``
        (batch dimension minor-most) the compiled step copied the whole
        set every minibatch, 13.5 of AlexNet's 38 ms, until
        ``_place_data`` asked the step's compiler where the set should
        lie (PERF.md sections 5 and 6, PR 29).  What a launch costs on
        the present chip is not measured."""
        self.gather_loader = loader
        loader.defer_device_gather = True
        return self

    def __getstate__(self):
        return mesh_mod.spec_in_state(super().__getstate__())

    # -- jit construction ----------------------------------------------------
    def initialize(self, device=None, **kwargs):
        self.mesh = mesh_mod.live_mesh(self.mesh)
        # forwards live outside the control graph in fused mode, so they
        # have not been initialized by the dependency walk — bring them up
        # in chain order (shapes propagate input→output)
        for fwd in self.forwards:
            if not fwd.is_initialized:
                fwd.initialize(device=device, **kwargs)
        super().initialize(**kwargs)
        self.device = device
        import jax
        import jax.numpy as jnp

        forwards = self.forwards
        gds = self.gd_units
        loss_kind = self.loss_kind
        # trace-time names only: nothing of them runs on the device
        scopes = scope_names(forwards)
        softmax_head = isinstance(forwards[-1], All2AllSoftmax)
        has_stochastic = any(f.stochastic for f in forwards)

        cdtype = self.compute_dtype
        if cdtype is not None:
            cdtype = jnp.dtype(cdtype)

        # what a unit may ask of the trainer (znicz/transformer.py):
        # FLOAT32_PARAMS, tensors the boundary cast leaves float32;
        # ``remat``, jax.checkpoint around its apply, and ``remat_saves``,
        # what that checkpoint keeps; ``apply_stats``, an apply that also
        # returns counters for the accumulator; ``update_buffers``, the
        # tensors it moves itself each train step, from those counters
        keep_f32 = [getattr(f, "FLOAT32_PARAMS", ()) for f in forwards]
        with_stats = [hasattr(f, "apply_stats") for f in forwards]
        appliers = [applier(f) for f in forwards]
        self._file_remat(scopes, keep_f32, cdtype)

        def net_body(params, x, seed):
            """The chain up to the last unit: (its input, the parameters
            as the chain computes with them, the units' counters)."""
            if cdtype is not None:
                # cast once at the boundary; XLA keeps everything in
                # compute dtype through the chain (MXU native rate)
                # (two forms: where no unit keeps a tensor float32 the
                # cast is traced exactly as it always was, so the older
                # models' programs and cache entries stay the same)
                if any(keep_f32):
                    params = [{k: p if k in keep else p.astype(cdtype)
                               for k, p in layer.items()}
                              for layer, keep in zip(params, keep_f32)]
                else:
                    params = jax.tree.map(lambda p: p.astype(cdtype),
                                          params)
                if jnp.issubdtype(x.dtype, jnp.floating):
                    x = x.astype(cdtype)
            h = x
            train = seed is not None
            if train and has_stochastic:
                key = jax.random.key(seed)
            stats = {}
            for i, fwd in enumerate(forwards[:-1]):
                # token ids are not cast (bfloat16 holds integers up to
                # 256); the rows they select are
                ids = not jnp.issubdtype(h.dtype, jnp.floating)
                with jax.named_scope(scopes[i]):
                    if train and fwd.stochastic:
                        h = fwd.apply_train(params[i], h,
                                            jax.random.fold_in(key, i))
                    elif with_stats[i]:
                        h, stats[scopes[i]] = appliers[i](params[i], h)
                    else:
                        h = appliers[i](params[i], h)
                if ids and cdtype is not None:
                    h = h.astype(cdtype)
            return h, params, stats

        def net_apply(params, x, with_logits, seed):
            h, params, _ = net_body(params, x, seed)
            last = forwards[-1]
            with jax.named_scope(scopes[-1]):
                if with_logits and softmax_head:
                    return last.apply_logits(params[-1], h)
                return last.apply(params[-1], h)

        def loss_fn(params, x, labels_or_targets, mask, seed=None):
            if loss_kind == "token":
                # the head takes the projection and the loss over blocks
                # of tokens: [tokens, V] float32 logits never exist whole
                h, params, stats = net_body(params, x, seed)
                with jax.named_scope(scopes[-1]):
                    total, wrong, pred = forwards[-1].token_loss(
                        params[-1], h, labels_or_targets, mask)
                tokens = jnp.maximum(
                    mask.sum() * labels_or_targets.shape[1], 1.0)
                return total / tokens, (pred, {
                    "n_err": wrong, "loss_sum": total, "units": stats})
            out = net_apply(params, x, True, seed)
            # the loss itself is f32: bf16 log-sum-exp/reduction noise
            # would feed straight into the gradients' scale
            with jax.named_scope("loss"):
                out = out.astype(jnp.float32)
                if loss_kind == "softmax":
                    data_loss = EvaluatorSoftmax.loss_from_logits(
                        out, labels_or_targets, mask)
                else:
                    data_loss = EvaluatorMSE.loss_from_output(
                        out, labels_or_targets, mask)
            return data_loss, out

        n_classes = int(self.forwards[-1].output.shape[-1]) \
            if loss_kind == "softmax" else 0
        self._n_classes = n_classes
        with_cm = self.compute_confusion_matrix
        if loss_kind == "softmax" and with_cm and not self.confusion_matrix:
            # int32 throughout: the running total lives on device (jax
            # default integer width), bounding any one [pred, true] cell
            # at 2^31 counts — i.e. >2 billion samples routed through a
            # single cell before wraparound, far past any other counter
            self.confusion_matrix.mem = numpy.zeros(
                (n_classes, n_classes), numpy.int32)
        self._cm_dev_ = None    # device-resident running total (flush)

        @jax.named_scope("metrics")
        def accumulate(macc, out, labels_or_targets, mask):
            """Fold one step's outputs into the device-resident metric
            accumulator.  Matches the graph evaluators' side-channels:
            softmax → (n_err, confusion[pred, true], max row |err| sum over
            probabilities); mse → (sum sample-mse, max rmse, min rmse)."""
            if loss_kind == "softmax":
                n, cm, mx = macc
                # exact integer count (float32 would lose counts past 2^24)
                pred = jnp.argmax(out, axis=-1)
                wrong = (pred != labels_or_targets) & (mask > 0)
                onehot = jax.nn.one_hot(labels_or_targets, n_classes,
                                        dtype=out.dtype)
                err_rows = jnp.abs(out - onehot).sum(axis=1) * mask
                if with_cm:
                    # scatter-add serializes on the TPU vector unit (a
                    # measured 22% hit on the MNIST scan bench); the same
                    # histogram as a one-hot outer product rides the MXU.
                    # float32 counts are exact here: per-step counts are
                    # bounded by the batch (< 2^24)
                    pred_oh = jax.nn.one_hot(
                        pred, n_classes, dtype=jnp.float32) * mask[:, None]
                    true_oh = jax.nn.one_hot(
                        labels_or_targets, n_classes, dtype=jnp.float32)
                    cm = cm + jnp.einsum(
                        "bi,bj->ij", pred_oh, true_oh).astype(jnp.int32)
                return (n + wrong.astype(jnp.int32).sum(), cm,
                        jnp.maximum(mx, err_rows.max()))
            sse, mx, mn = macc
            err = (out - labels_or_targets).reshape(out.shape[0], -1)
            sample_mse = (err * err).mean(axis=1)
            rmse = jnp.sqrt(sample_mse)
            valid = mask > 0
            return (sse + (sample_mse * mask).sum(),
                    jnp.maximum(mx, jnp.where(valid, rmse, -jnp.inf).max()),
                    jnp.minimum(mn, jnp.where(valid, rmse, jnp.inf).min()))

        def observable(out):
            """What consumers linked to ``output`` see: probabilities for a
            softmax head (graph-mode All2AllSoftmax.output parity), raw
            output otherwise.  The loss itself consumed the logits."""
            return jax.nn.softmax(out) if softmax_head else out

        def fold(macc, out, labels_or_targets, mask):
            """(accumulator with this step folded in, observable
            output).  A token loss brings its counts with it (wrong
            tokens, summed loss, the units' counters: no one-hot, no
            confusion matrix) and shows the predicted ids."""
            if loss_kind == "token":
                pred, counts = out
                with jax.named_scope("metrics"):
                    return jax.tree.map(jnp.add, macc, counts), pred
            out = observable(out)
            return accumulate(macc, out, labels_or_targets, mask), out

        def train_step(params, opt, macc, x, y, size, seed, lr_scale):
            mask = (jnp.arange(x.shape[0]) < size).astype(jnp.float32)
            (loss, out), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, x, y, mask, seed)
            new_params, new_opt = [], []
            counters = out[1]["units"] if loss_kind == "token" else {}
            for i, gd in enumerate(gds):
                layer_p, layer_o = {}, {}
                # buffers a forward unit moves itself, from the counters
                # its own apply_stats returned this step
                if scopes[i] in counters and hasattr(forwards[i],
                                                     "update_buffers"):
                    with jax.named_scope("update/" + scopes[i]):
                        layer_p = dict(forwards[i].update_buffers(
                            params[i], counters[scopes[i]]))
                    layer_o = {name: opt[i][name] for name in layer_p}
                for name, p in params[i].items():
                    if name in layer_o:
                        continue
                    with jax.named_scope("update/" + scopes[i]):
                        g = grads[i][name]
                        decay, l1l2, ortho = gd.decay_for(name)
                        g = solvers.regularized_grad(g, p, decay, l1l2,
                                                     jnp, ortho)
                        # lr_scale: DYNAMIC schedule knob
                        # (LearningRateAdjuster) — an argument, not a
                        # constant, so per-epoch decay never retraces
                        delta, st = gd.solver.update(
                            g, p, opt[i][name],
                            gd.lr_for(name) * lr_scale, jnp)
                        layer_p[name] = p + delta
                        layer_o[name] = st
                new_params.append(layer_p)
                new_opt.append(layer_o)
            macc, out = fold(macc, out, y, mask)
            return new_params, new_opt, macc, loss, out

        def eval_step(params, macc, x, y, size):
            mask = (jnp.arange(x.shape[0]) < size).astype(jnp.float32)
            loss, out = loss_fn(params, x, y, mask)
            macc, out = fold(macc, out, y, mask)
            return macc, loss, out

        # the metric accumulator stays ON DEVICE between steps and is
        # flushed to the host only at class boundaries — per-step int()
        # pulls would serialize the pipeline on a device sync.  int32 for
        # error counts (exact); float32 for mse sums (flushed per class,
        # so drift stays bounded by one epoch)
        self._unit_stats_ = {
            scopes[i]: f.stats_shapes() for i, f in enumerate(forwards[:-1])
            if with_stats[i]}
        self._placement_ = None
        self._macc_ = self._macc_init()
        # copy: the step donates its param buffers, so they must not alias
        # the forward units' live weight Arrays
        self._params_ = [
            {k: jnp.array(v) for k, v in fwd.params.items()}
            for fwd in forwards]
        # solver state: restored from the GD units' pickled state when
        # resuming a snapshot, else freshly initialized
        self._opt_ = [
            {name: (tuple(jnp.asarray(s) for s in
                          gd.solver_state[name])
                    if gd.solver_state.get(name) else
                    gd.solver.init(p, jnp))
             for name, p in self._params_[i].items()}
            for i, gd in enumerate(gds)]
        if self.mesh is not None:
            self._placement_ = place = mesh_mod.TrainerPlacement(
                self.mesh, self._params_, self._opt_, self.data_axis,
                self.model_axis, self.tp_mode,
                workflow=getattr(self._workflow, "name", "-"))
            self._params_ = place.place(self._params_, "param")
            self._opt_ = place.place(self._opt_, "opt")
            self._macc_ = place.place(self._macc_, "rep")
        #: the two raw step functions (the epoch scan loops over them)
        self._step_fns_ = train_step, eval_step
        # the chain's evaluation-mode output (logits for a softmax or a
        # token head) as a function of the parameters: what a comparison
        # with a reference reads; compiled only if called
        self._forward_ = jax.jit(
            lambda params, x: net_apply(params, x, True, None))
        # ``size``, ``seed`` and ``lr_scale`` stay DYNAMIC (replicated
        # scalars): a static size would recompile the step for every
        # distinct tail batch.  Across processes the minibatch leaves
        # the loader as a process-local array (argnums 3, 4 / 2, 3)
        steps_place = self._placement_ if self.DISPATCHES_STEPS else None
        self._train_step_ = jit_program(
            steps_place, train_step,
            ("param", "opt", "rep", "batch", "batch", "rep", "rep", "rep"),
            ("param", "opt", "rep", "rep", "batch"),
            donate_argnums=(0, 1, 2), host_argnums=(3, 4))
        self._eval_step_ = jit_program(
            steps_place, eval_step,
            ("param", "rep", "batch", "batch", "rep"),
            ("rep", "rep", "batch"), donate_argnums=(1,),
            host_argnums=(2, 3))
        # the gather-in-step path needs the dataset resident on a real
        # device; numpy/force-numpy loaders fill minibatch_data host-side
        # instead, so fall back to the plain step there
        self._use_gather_ = (self.gather_loader is not None and
                             getattr(self.gather_loader, "_use_device",
                                     False))
        if self._use_gather_:
            self._hold_resident_set(self.gather_loader)

            def train_step_g(data, y_all, params, opt, macc, idx, size,
                             seed, lr_scale):
                with jax.named_scope("gather"):
                    x = jnp.take(data, idx, axis=0)
                    y = jnp.take(y_all, idx, axis=0)
                return train_step(params, opt, macc, x, y, size, seed,
                                  lr_scale)

            def eval_step_g(data, y_all, params, macc, idx, size):
                with jax.named_scope("gather"):
                    x = jnp.take(data, idx, axis=0)
                    y = jnp.take(y_all, idx, axis=0)
                return eval_step(params, macc, x, y, size)

            self._train_step_g_ = jax.jit(train_step_g,
                                          donate_argnums=(2, 3, 4))
            self._eval_step_g_ = jax.jit(eval_step_g, donate_argnums=(3,))
        # persistent executable cache (compilecache subsystem): wrap the
        # per-step programs that are plain jits so an ElasticRunner
        # respawn / snapshot restore deserializes yesterday's executable
        # instead of recompiling (the placed programs and the scans stay
        # jits: ROADMAP D14); a step the compiler refuses raises there
        # too.  No configured cache dir = exactly the code above (the
        # gather steps apart: _place_data)
        cache = default_cache()
        if cache is not None and steps_place is None:
            self._train_step_ = AotStep(self._train_step_, cache,
                                        "fused.train_step")
            self._eval_step_ = AotStep(self._eval_step_, cache,
                                       "fused.eval_step")
        if self._use_gather_:
            self._place_data()

    def _file_remat(self, scopes, keep_f32, cdtype):
        """The record of what the step's checkpoints do, filed once as
        the step is built (span ``step.remat``): the units under a
        ``jax.checkpoint`` (``units``, by scope), what each keeps across
        it (``saves``: ``scope:name+name``) and the bytes a train step
        holds for that from forward to backward (``bytes``; the span's
        length is the trace that found them), and what a unit says
        tells it from its neighbours of the same class (``notes``:
        ``scope:remat_note``, an attention block's window and rotary
        type).  A chain with no checkpointed unit files nothing."""
        under = [(scope, fwd, keep) for scope, fwd, keep in zip(
            scopes, self.forwards, keep_f32) if getattr(fwd, "remat", False)]
        if not under:
            return
        saving = [(scope, fwd, keep) for scope, fwd, keep in under
                  if getattr(fwd, "remat_saves", ())]
        with events.timed(
                "step.remat", units=",".join(scope for scope, _, _ in under),
                saves=" ".join("%s:%s" % (scope, "+".join(fwd.remat_saves))
                               for scope, fwd, _ in saving),
                notes=" ".join("%s:%s" % (scope, fwd.remat_note)
                               for scope, fwd, _ in under
                               if hasattr(fwd, "remat_note"))) as span:
            span.count(bytes=sum(saved_bytes(fwd, keep, cdtype)
                                 for _, fwd, keep in saving))

    def _hold_resident_set(self, ld):
        """The loader's resident set and its labels, for the programs
        that gather their minibatches on the device (the gather step,
        the scans).  They ride as ARGUMENTS: a closed-over jax.Array
        would be baked into the HLO as a literal, bloating the
        executable by the whole set (see loader/fullbatch.py).  Over a
        mesh the set is replicated: every shard gathers its own rows."""
        import jax
        self._data_dev_ = ld.original_data.devmem
        if self.loss_kind == "softmax":
            self._y_dev_ = jax.device_put(ld._dense_labels)
        else:
            self._y_dev_ = ld.original_targets.devmem
        if self._placement_ is not None:
            self._data_dev_, self._y_dev_ = self._placement_.place(
                (self._data_dev_, self._y_dev_), "rep")

    def _lower_gather_train(self, data, *rest):
        """``train_step_g`` lowered for the arguments ``(data, *rest)``
        (arrays or ``ShapeDtypeStruct``s), the layout of ``data`` left to
        the compiler.  Like a jit's own dispatch, only a committed
        ``data`` brings its sharding: the outputs then stay uncommitted
        where they were, and no program downstream sees another
        signature."""
        import jax
        from jax.experimental.layout import Format, Layout
        home = data.sharding if getattr(data, "committed", True) else None
        auto = jax.jit(
            self._train_step_g_.__wrapped__, donate_argnums=(2, 3, 4),
            in_shardings=(Format(Layout.AUTO, home),) + (None,) * len(rest))
        return auto.lower(jax.ShapeDtypeStruct(data.shape, data.dtype),
                          *rest)

    def _place_data(self):
        """Compile the gather train step now, asking ITS compiler where
        the resident set should lie, and place the set there once.

        ``device_put`` and a jit's output leave an array in the device's
        default layout.  For ``f32[8704, 227, 227, 3]`` on a v5e that one
        has the batch dimension minor-most, rows cannot be gathered from
        it, and every program that gathers first copied the WHOLE set:
        13.5 of AlexNet's 38 ms a step (PERF.md section 6, PR 29).  So
        the program is lowered with that one argument's layout left open
        (``Layout.AUTO``), the executable says which it took, and the set
        moves there if it lies otherwise and both placements fit on the
        device for the moment of the copy (5.50 + 6.20 GB there, 0.18 s).
        Nothing is decided here from shapes, models or platforms: a set
        that lies as asked (every CPU run) is left alone, and the
        executable compiled here is the step's one train program either
        way.

        Both gather steps are AOT executables (``AotStep``, with or
        without a store): the train step because a layout left open can
        only be compiled ahead; the evaluation step, compiled at its
        first call against the set as it lies, because a placed set is a
        COMMITTED array, and a jit, which specialises on that, would
        compile once for a fresh accumulator and once for its own
        output."""
        import jax
        from jax.experimental.layout import Format
        ld, data = self.gather_loader, self._data_dev_
        scalar = jax.ShapeDtypeStruct((), numpy.int32)
        cache = default_cache()
        name = "fused.train_step_gather"
        train = AotStep(self._train_step_g_, cache, name)
        compiled = train.compile(self._lower_gather_train(
            data, self._y_dev_, self._params_, self._opt_, self._macc_,
            jax.ShapeDtypeStruct((ld.max_minibatch_size,), ld.INDEX_DTYPE),
            scalar, scalar, jax.ShapeDtypeStruct((), numpy.float32)))
        asked = compiled.input_formats[0][0].layout
        with events.timed("step.place_data", layout=str(asked)) as span:
            before, lay = data.on_device_size_in_bytes(), data.format.layout
            room = asked == lay or self._room_to_place(data, compiled)
            if asked != lay and room:
                # the relayout program's result has the layout asked for,
                # which JAX's persistent cache would not give back
                with compiles_not_persisted():
                    data = jax.block_until_ready(jax.device_put(
                        data, Format(asked, data.sharding), donate=True))
                ld.original_data.replace_devmem(data)
                self._data_dev_ = data
            span.count(bytes_before=before,
                       placed=data.format.layout != lay,
                       bytes_after=data.on_device_size_in_bytes())
        if asked != data.format.layout:
            # today's placement and today's program, compiled at the
            # first call: slower, but a set that fitted before does not
            # fail now
            logging.getLogger(type(self).__name__).warning(
                "the step's compiler asks for the resident set in layout "
                "%s, but %s: it stays in %s and every step copies it", asked,
                "the copy came back as the set went" if room else
                "the device has no room for two copies of it",
                data.format.layout)
            train = AotStep(self._train_step_g_, cache, name)
        self._train_step_g_ = train
        self._eval_step_g_ = AotStep(self._eval_step_g_, cache,
                                     "fused.eval_step_gather")

    @staticmethod
    def _room_to_place(data, compiled):
        """Whether the device holding ``data`` has room for a second
        placement of it beside all it holds now.  The new one is counted
        as no larger than ALL the arguments of the ``compiled`` step
        together, of which it is one.  A device that reports no memory
        statistics (the CPU) has room."""
        stats = next(iter(data.devices())).memory_stats()
        if not stats:
            return True
        need = compiled.memory_analysis().argument_size_in_bytes
        return stats["bytes_in_use"] + need <= stats["bytes_limit"]

    def _macc_init(self):
        """Fresh on-device metric accumulator pytree, placed over a
        mesh like the one the programs return."""
        import jax.numpy as jnp
        if self.loss_kind == "token":
            macc = {"n_err": jnp.zeros((), jnp.int32),
                    "loss_sum": jnp.zeros((), jnp.float32),
                    "units": {scope: {name: jnp.zeros(shape, jnp.int32)
                                      for name, shape in shapes.items()}
                              for scope, shapes in
                              self._unit_stats_.items()}}
        elif self.loss_kind == "softmax":
            c = self._n_classes if self.compute_confusion_matrix else 0
            macc = (jnp.zeros((), jnp.int32),
                    jnp.zeros((c, c), jnp.int32),
                    jnp.zeros((), jnp.float32))
        else:
            macc = (jnp.zeros((), jnp.float32),
                    jnp.zeros((), jnp.float32),
                    jnp.full((), jnp.inf, jnp.float32))
        if self._placement_ is None:
            return macc
        return self._placement_.fresh_accumulator(macc)

    # -- run -----------------------------------------------------------------
    def _staged_seed_arg(self):
        """Device-resident seed scalar for the staged gather path.  The
        per-step H2D of the (tiny) seed is a host sync point (its cost
        on the present chip: not measured); instead the NEXT train seed
        is device_put right after each dispatch, so the transfer rides
        under the in-flight step's compute.  Falls back to a synchronous
        device_put when nothing is staged (first step, restored run)."""
        import jax
        staged = getattr(self, "_staged_seed_", None)
        if staged is not None and staged[0] == self._seed_counter:
            arg = staged[1]
        else:
            arg = jax.device_put(numpy.int32(self._seed_counter))
        nxt = (self._seed_counter + 1) % 0x7FFF0000
        self._staged_seed_ = (nxt, jax.device_put(numpy.int32(nxt)))
        return arg

    def run(self):
        size = int(self.minibatch_size)
        cls = self.minibatch_class
        events.set_work(self.epoch_number)
        with events.timed("step.run", cls=loader_mod.CLASS_NAME[cls],
                          epoch=self.epoch_number) as span:
            span.count(**self._work(1, size))
            self._run_minibatch(size, cls == loader_mod.TRAIN)
            if bool(self.last_minibatch):
                self._finish_class()

    @property
    def labels_per_sample(self):
        """Labels a sample carries: a sequence's tokens under the token
        loss (``n_err`` then counts wrong tokens), else one."""
        if self.loss_kind == "token":
            return int(self.forwards[0].input_shape[1])
        return 1

    def _work(self, steps, images):
        """What a ``step.run`` span counts: steps and samples, and for a
        token loss the tokens those samples hold."""
        work = {"steps": steps, "images": images}
        if self.loss_kind == "token":
            work["tokens"] = images * self.labels_per_sample
        return work

    def _run_minibatch(self, size, train):
        """Hand one minibatch to the jitted step (``step.dispatch``: the
        argument hand-over and the enqueue; the device runs on)."""
        if getattr(self, "_use_gather_", False):
            # a MinibatchPrefetcher stages idx/size on device ahead of
            # the step (the H2D overlapped the previous step's compute);
            # the synchronous path passes host values exactly as before
            staged = getattr(self.gather_loader, "prefetch_staged_", None)
            if staged is not None:
                idx, size_arg = staged
            else:
                idx, size_arg = self.gather_loader._padded_indices_, size
            if train:
                self._seed_counter = (self._seed_counter + 1) % 0x7FFF0000
                seed_arg = (self._staged_seed_arg() if staged is not None
                            else self._seed_counter)
            with events.timed("step.dispatch"):
                if train:
                    (self._params_, self._opt_, self._macc_, loss, out) = \
                        self._train_step_g_(
                            self._data_dev_, self._y_dev_, self._params_,
                            self._opt_, self._macc_, idx, size_arg,
                            seed_arg, float(self.lr_scale))
                else:
                    self._macc_, loss, out = self._eval_step_g_(
                        self._data_dev_, self._y_dev_, self._params_,
                        self._macc_, idx, size_arg)
        else:
            x = self.minibatch_data.devmem
            if self.loss_kind == "softmax":
                y = self.minibatch_labels.devmem
            else:
                y = self.minibatch_targets.devmem
            if train:
                self._seed_counter = (self._seed_counter + 1) % 0x7FFF0000
            with events.timed("step.dispatch"):
                if train:
                    (self._params_, self._opt_, self._macc_, loss, out) = \
                        self._train_step_(
                            self._params_, self._opt_, self._macc_, x, y,
                            size, self._seed_counter, float(self.lr_scale))
                else:
                    self._macc_, loss, out = self._eval_step_(
                        self._params_, self._macc_, x, y, size)
        self.loss = loss           # device scalars; pulled lazily
        self.output.devmem = out

    def _finish_class(self):
        """The class-end epilogue, in the order that keeps the device fed.
        The class's last dispatch is still running when this is called, so
        all the device has to do next is enqueued behind it first — the
        weight copies into the forward units, the fresh accumulator, the
        scalars' copy to the host — and the host blocks on the class's
        numbers last (``step.read_metrics``), in this same ``run()``:
        Decision sees them exactly when it did.  Blocking first left the
        device's queue empty through the read and all that followed it,
        twice an epoch (PERF.md section 6, PR 27)."""
        self.sync_weights()
        self._flush_metrics()

    def _flush_metrics(self):
        """Fold the device accumulator into the evaluator-compatible
        Arrays (one sync per class boundary, not per step).  Its
        successor is made before the read, so the next dispatch never
        waits for it."""
        with events.timed("step.flush_metrics"):
            done, self._macc_ = self._macc_, self._macc_init()
            self._pull_metrics(done)

    def _pull_metrics(self, macc):
        """File what the accumulator ``macc`` holds: the confusion matrix
        on the device, the scalars through one blocking read."""
        if self.loss_kind == "token":
            import jax
            got = self._read_scalars(macc)
            self.n_err.map_write()[0] += int(got["n_err"])
            self.metrics.map_write()[0] += float(got["loss_sum"])
            # the units' counters (per-expert token counts, rows of the
            # grouped product), summed per class since the workflow
            # started
            name = loader_mod.CLASS_NAME[self.minibatch_class]
            totals = self.unit_stats.get(name) or jax.tree.map(
                lambda a: numpy.zeros(a.shape, numpy.int64), got["units"])
            self.unit_stats[name] = jax.tree.map(
                lambda total, new: total + new, totals, got["units"])
        elif self.loss_kind == "softmax":
            n_err, cm, maxerr = macc
            if self.compute_confusion_matrix:
                # the [C, C] matrix stays ON DEVICE: pulling it per class
                # boundary costs C²·4 bytes of D2H (4 MB for ImageNet
                # heads; not measured on the present chip); instead the
                # running total accumulates device-side and the Array
                # transfers it lazily only when someone map_read()s it
                if self._cm_dev_ is None:
                    host = self.confusion_matrix.mem
                    if host is not None and host.any():
                        import jax.numpy as jnp  # resumed: seed from host
                        self._cm_dev_ = jnp.asarray(
                            host.astype(numpy.int32)) + cm
                    else:
                        self._cm_dev_ = cm
                else:
                    self._cm_dev_ = self._cm_dev_ + cm
                self.confusion_matrix.devmem = self._cm_dev_
            n_err, maxerr = self._read_scalars((n_err, maxerr))
            self.n_err.map_write()[0] += int(n_err)
            self.max_err_output_sum.map_write()[0] = max(
                float(self.max_err_output_sum[0]), float(maxerr))
        else:
            sse, mx, mn = self._read_scalars(macc)
            m = self.metrics.map_write()
            m[0] += float(sse)
            m[1] = max(m[1], float(mx))
            m[2] = min(m[2], float(mn))

    @staticmethod
    def _read_scalars(scalars):
        """The class's one blocking read.  The copies to the host are
        started first and queue behind the running dispatch; the read
        rides ONE batched device_get (per-leaf reads are a device sync
        each) under a span of its own: how long the host waited for the
        device, which is the scan's remaining time where the epilogue was
        enqueued in time."""
        import jax
        for leaf in jax.tree.leaves(scalars):
            leaf.copy_to_host_async()
        with events.timed("step.read_metrics"):
            return jax.device_get(scalars)

    def sync_weights(self):
        """Reflect the fused params back into the forward units' Arrays:
        one ``jnp.array`` copy on the device per tensor (sixteen launches
        for AlexNet) — the fused buffers get donated by the next step and
        must not be aliased externally.  At a class end it is called with
        the dispatch still running, so the copies queue behind it; called
        on its own (snapshot, rollback, the workflow's end) it costs the
        host 5.5 ms on an idle chip (PERF.md section 5, PR 25)."""
        import jax.numpy as jnp
        with events.timed("step.sync_weights"):
            for fwd, p in zip(self.forwards, self._params_):
                fwd.set_params({k: jnp.array(v) for k, v in p.items()})

    def sync_solver_state(self):
        """Pull the fused optimizer state into the GD units' picklable
        ``solver_state`` (host numpy) — called before snapshotting so a
        resumed run continues with intact momentum/accumulators."""
        import numpy
        for gd, layer in zip(self.gd_units, self._opt_):
            for name, state in layer.items():
                gd.solver_state[name] = tuple(
                    numpy.asarray(s) for s in state)

    def get_metric_values(self):
        return {"n_err": int(self.n_err[0]),
                "loss": None if self.loss is None else float(self.loss)}

    def make_trace(self):
        """The hand-fused step is already ONE compiled, donated program:
        under whole-workflow compilation it reports as a pre-compiled
        region of its own (one producer of traced regions, not a special
        case) and keeps executing natively, per minibatch or per class,
        its in-program shardings (and the all-reduce XLA derives from
        them) untouched by the graph compiler."""
        from ..graphcomp.faces import OpaqueFace
        over = "" if self.mesh is None else \
            ", SPMD over the %r mesh axes" % list(self.mesh.axis_names)
        return OpaqueFace(self, "hand-fused train step: %s%s"
                          % (self.DISPATCH, over))
