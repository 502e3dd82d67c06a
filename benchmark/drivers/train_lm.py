"""The ``train_lm`` driver: a language-model training configuration
through the program's own command-line driver (``veles_tpu.__main__.Main``
and ``Launcher``), for whole epochs over a window of the host clock.  The
window logic is the ``train`` driver's (warm epochs, a window cut at whole
epochs, ``EpochClock``, the ``bench.train.*`` annotations the span readers
anchor on); what differs is what is compared with the reference.

**What a configuration brings** (``configs/<name>/``), so that the next
language model adds files only:

- ``config.json``: ``driver: "train_lm"``, ``config_namespace``,
  ``compute_dtype``, ``precision_level``, ``solver`` (read by its
  reference), ``data.sequence_length``, and the model's keys;
- ``workflow.py``: a ``StandardWorkflow`` with the ``token`` loss whose
  loader serves token ids ``[N, S]`` and next-token labels ``[N, S]``
  resident on the device, made from ``<namespace>.loader.seed``, with an
  ``EpochClock`` unit as ``wf.epoch_clock``; ``<namespace>.model`` holds
  the keys a rehearsal overrides;
- ``reference.py``: ``forward(config, params, ids, precision)`` ->
  float32 logits, ``token_loss(logits, labels)``, ``adamw_steps(config,
  params, ids, labels, steps, precision)`` -> (parameters, first
  moments, second moments); ``params`` is the program's own list of
  per-unit dictionaries; ``precision`` is ``"highest"`` (the mathematics)
  or ``"default"`` (the arithmetic the configuration states);
- ``work.py``: ``train_flops_per_image`` (an "image" is one sequence) and
  the kernels' operations and bytes.

**Counters** (``run.counters``): ``images`` (sequences), ``tokens``,
``train_steps``, ``images_per_step``, ``epochs``, ``setup_compile_s``,
``tokens_per_s`` and, where the step's units count (``unit_stats``), the
window's ``moe_rows_train`` / ``moe_rows_valid`` and
``expert_load_max_over_mean``.

**Correctness**, outside the window, at the timed sizes, on the first
``minibatch`` train sequences.  The solver state and the forward units'
weight copies are FREED first (the reference needs the room): (a) the
log-probabilities of the chain's own evaluation-mode forward
(``step._forward_``) against ``reference.forward`` at ``highest`` and in
the stated arithmetic, under the trained parameters and under probe
parameters of unit gain; (b) the loss of the step's evaluation function
(the head's blocked loss); (c) ``UPDATE_STEPS`` steps of the step's own
train function from the probe parameters against
``reference.adamw_steps`` in the stated arithmetic: every tensor's
change and both moments, the worst tensor deciding; (d) dropless: rows
the grouped product computed equal rows routed; (e) no compile in the
window, a finite and falling loss.
"""

import math
import os

#: System against the reference at ``highest`` precision: rms of the
#: difference of the centred log-probabilities over their spread.  The
#: system multiplies bfloat16 operands and keeps bfloat16 activations
#: between blocks, the reference float32 in six passes; some tokens also
#: change their sixth expert where two router scores lie within the
#: rounding.  Measured on the chip under the probe parameters over six
#: seeds: 3.4e-2 to 4.2e-2 (trained parameters: 1.7e-3 to 1.5e-2); the
#: reference with float8 operands against this one: 0.39-0.40 (PERF.md
#: section 6, PR 28).
LOGIT_TOLERANCE = 1.2e-1
#: System against the reference in the STATED arithmetic (bfloat16
#: operands and activations, float32 sums, router, norms and loss).
#: Measured under the probe parameters over six seeds: 3.1e-2 to 3.7e-2
#: (two bfloat16 computations that round at different points differ by
#: about as much as each does from the mathematics); the reference with
#: float8 operands, the nearest precision below, against this one:
#: 0.39-0.40, which the limit refuses with four times of room.
STATED_LOGIT_TOLERANCE = 1e-1
#: the loss, a mean over 16,384 tokens of the same quantities, as a share
#: of the loss, against the reference in the stated arithmetic.
#: Measured: 4e-6 to 5e-5; the float8 reference's loss: 4e-3 to 9e-3 off
LOSS_TOLERANCE = 1e-3
#: AdamW's moments after UPDATE_STEPS steps, per tensor, rms of the
#: difference over the rms of the reference's own: the first moment is
#: the gradient's running mean, so this is the gradient's error.  For
#: every tensor outside the expert blocks.  Measured on the chip over
#: four seeds: 0.02-0.09; the reference with float8 operands: 1.0 on
#: every tensor (PERF.md section 6, PR 28)
MOMENT_TOLERANCE = 2.5e-1
#: and for the expert blocks' own tensors (router, routed and shared
#: experts).  Top-k routing is discontinuous and the tokens repeat (Zipf:
#: the commonest id is a tenth of the batch): where that id's sixth and
#: seventh scores lie within the rounding, system and reference send ALL
#: its occurrences to different experts, and a held expert's gradient
#: gains or loses twice its usual tokens.  Measured: 0.04-0.13 on three
#: seeds, 0.52-0.53 on the fourth (twice, the same layer); float8: 1.0
ROUTED_MOMENT_TOLERANCE = 8e-1
#: and each tensor's change.  Adam's first steps move every weight by
#: about the learning rate in the direction of the gradient's SIGN, so
#: where a gradient's error exceeds its size the change flips by twice
#: the rate: the limit is on how much of a tensor may do so.  Measured:
#: 0.39-0.44 at worst (a router), 0.11-0.13 the median tensor; float8:
#: 1.9 at worst, 1.4 the median tensor
CHANGE_TOLERANCE = 8e-1
#: train steps of the update comparison: the second starts from moments
#: that are not zero
UPDATE_STEPS = 2


def _fixed_minibatch(wf, batch):
    """The first ``batch`` train sequences and their labels."""
    import jax.numpy as jnp
    import numpy
    from veles_tpu import loader as loader_mod
    step, ld = wf.fused_step, wf.loader
    first = ld.class_end_offsets[loader_mod.VALID]
    idx = numpy.arange(first, first + batch, dtype=ld.INDEX_DTYPE)
    return (jnp.take(step._data_dev_, idx, axis=0),
            jnp.take(step._y_dev_, idx, axis=0))


def _system_loss(wf, params):
    """The loss of the step's own evaluation function under ``params``
    on the fixed minibatch, and the counters its units returned.  Where
    the step scans, it is the epoch's own evaluation scan over one
    minibatch (the program the validation pass runs: nothing more to
    compile when a validation pass is one step long)."""
    import jax
    import numpy
    from veles_tpu import loader as loader_mod
    step, ld = wf.fused_step, wf.loader
    batch = ld.max_minibatch_size
    first = ld.class_end_offsets[loader_mod.VALID]
    idx = numpy.arange(first, first + batch, dtype=ld.INDEX_DTYPE)
    if hasattr(step, "_eval_scan_"):
        macc, losses = step._eval_scan_(
            step._data_dev_, step._y_dev_, params, step._macc_init(),
            idx[None], numpy.asarray([batch], numpy.int32))
        loss = losses[-1]
    else:
        macc, loss, _ = step._eval_step_(
            params, step._macc_init(), *_fixed_minibatch(wf, batch),
            numpy.int32(batch))
    return float(loss), jax.device_get(macc)


def probe_parameters(params, seed):
    """Parameters of the shapes of ``params`` at unit gain: matrices
    normal with variance 1 / fan-in (the embedding: variance 1), norms'
    weights 1 + 0.1 normal, the router's bias normal 0.01.  Every block
    then moves the residual stream by about its own size, so a wrong
    weight anywhere shows in the logits."""
    import jax
    import jax.numpy as jnp
    key = jax.random.key(int(seed) & 0x7FFFFFFF)

    @jax.jit
    def draw(key):
        out = []
        for i, layer in enumerate(params):
            new = {}
            for j, (name, p) in enumerate(sorted(layer.items())):
                k = jax.random.fold_in(jax.random.fold_in(key, i), j)
                noise = jax.random.normal(k, p.shape, jnp.float32)
                if p.ndim == 1:
                    new[name] = 0.01 * noise if "bias" in name \
                        else 1.0 + 0.1 * noise
                elif i == 0:
                    new[name] = noise
                else:
                    new[name] = noise / math.sqrt(p.shape[-2])
            out.append(new)
        return out
    return draw(key)


def _free(tree):
    import jax
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "delete") and not leaf.is_deleted():
            leaf.delete()


class Reference:
    """The configuration's plain reference as jitted functions of the
    parameters, and the error measures, on the cell's first chip."""

    def __init__(self, run):
        import jax
        import jax.numpy as jnp
        reference = run.config_module("reference")
        config = run.config

        def outputs(precision, params, x, y):
            logits = reference.forward(config, params, x, precision)
            return reference.token_loss(logits, y), logits
        self.outputs = {
            precision: jax.jit(lambda p, x, y, precision=precision:
                               outputs(precision, p, x, y))
            for precision in ("highest", "default")}
        # one step a call, the state handed on and donated: two steps
        # in one program would hold every moment twice
        self.update_step = jax.jit(
            lambda p, m, v, done, x, y: reference.adamw_steps(
                config, p, x, y, 1, "default", state=(m, v, done)),
            donate_argnums=(0, 1, 2))

        @jax.jit
        def logit_error(got, want):
            """rms of the difference of the centred log-probabilities
            over the spread of the reference's, and that spread."""
            def centred(z):
                z = jax.nn.log_softmax(z, axis=-1)
                return z - z.mean(axis=-1, keepdims=True)
            a, b = centred(got), centred(want)
            spread = jnp.sqrt(jnp.mean(b * b))
            return jnp.sqrt(jnp.mean((a - b) ** 2)) / spread, spread
        self.logit_error = logit_error

        @jax.jit
        def relative_rms(got, want):
            got, want = got.astype(jnp.float32), want.astype(jnp.float32)
            return jnp.sqrt(jnp.mean((got - want) ** 2)) / jnp.maximum(
                jnp.sqrt(jnp.mean(want ** 2)), 1e-30)
        self.relative_rms = relative_rms


def compare_outputs(reference, wf, params, x, y, tag):
    """{check: (ok, detail)} of the system's logits and loss under
    ``params`` against the reference given the same parameters."""
    import jax
    step = wf.fused_step
    loss, _ = _system_loss(wf, params)
    logits = step._forward_(params, x)
    checks = {}
    for precision, name, limit in (
            ("highest", "reference", LOGIT_TOLERANCE),
            ("default", "stated_precision", STATED_LOGIT_TOLERANCE)):
        ref_loss, ref_logits = reference.outputs[precision](params, x, y)
        err, spread = jax.device_get(
            reference.logit_error(logits, ref_logits))
        del ref_logits
        checks["%s_logits.%s" % (name, tag)] = (
            bool(err <= limit),
            "centred log-probabilities differ by %.2e rms of their "
            "spread %.3g, tolerance %g" % (err, spread, limit))
        if precision == "default":
            ref_loss = float(ref_loss)
            tolerance = LOSS_TOLERANCE * max(1.0, abs(ref_loss))
            checks["reference_loss." + tag] = (
                abs(ref_loss - loss) <= tolerance,
                "system %.6f, reference in the stated arithmetic %.6f, "
                "tolerance %.2g" % (loss, ref_loss, tolerance))
    return checks


def system_update(wf, params, x, y, steps):
    """(parameters, solver state) after ``steps`` calls of the step's
    own train function on the one batch, from ``params`` (consumed: the
    step donates them) and a fresh solver state: the function the epoch
    scan runs per minibatch, jitted alone."""
    import jax.numpy as jnp
    import numpy
    step = wf.fused_step
    opt = [{name: gd.solver.init(p, jnp) for name, p in layer.items()}
           for gd, layer in zip(step.gd_units, params)]
    macc = step._macc_init()
    for _ in range(steps):
        # at the schedule's peak rate (lr_scale 1), the reference's
        params, opt, macc, _, _ = step._train_step_(
            params, opt, macc, x, y, numpy.int32(x.shape[0]), None, 1.0)
    return params, opt


def compare_updates(reference, wf, probe, x, y):
    """{check: (ok, detail)} of the system's own train steps from the
    probe parameters against the reference's AdamW in the stated
    arithmetic: every tensor's change and both moments.  The system's
    results wait on the host while the reference runs (the device does
    not hold both)."""
    import jax
    import jax.numpy as jnp
    params, opt = system_update(wf, probe(), x, y, UPDATE_STEPS)
    mine = jax.device_get({
        "change": jax.tree.map(jnp.subtract, params, probe()),
        "m": [{n: s[0] for n, s in layer.items()} for layer in opt],
        "v": [{n: s[1] for n, s in layer.items()} for layer in opt]})
    _free((params, opt))
    # the reference's step is given the device alone: parameters and
    # moments donated from step to step, the start made anew afterwards
    ref_params = probe()
    ref_m = jax.tree.map(jnp.zeros_like, ref_params)
    ref_v = jax.tree.map(jnp.zeros_like, ref_params)
    for done in range(UPDATE_STEPS):
        ref_params, ref_m, ref_v = reference.update_step(
            ref_params, ref_m, ref_v, jnp.float32(done), x, y)
    theirs = {"change": jax.tree.map(jnp.subtract, ref_params, probe()),
              "m": ref_m, "v": ref_v}
    _free(ref_params)
    errors = {"change": [], "m": [], "v": []}       # [(error, tensor)]
    names = [type(f).MAPPING + ":" + f.name for f in wf.fused_step.forwards]
    for kind in errors:
        for i, layer in enumerate(theirs[kind]):
            for name, want in layer.items():
                if not bool(jnp.any(want != 0)):
                    continue        # a buffer: no gradient, no change
                err = float(reference.relative_rms(
                    jnp.asarray(mine[kind][i][name]), want))
                errors[kind].append((err, "%s %s" % (names[i], name)))
    def worst(kinds, routed):
        """(error, tensor) of the worst tensor of ``kinds`` among the
        expert blocks' tensors (``routed``) or the others."""
        return max((e for kind in kinds for e in errors[kind]
                    if e[1].startswith("expert_block") == routed),
                   default=(0.0, "none"))

    def median(kind):
        return sorted(errors[kind])[len(errors[kind]) // 2][0]
    plain, routed = worst("mv", False), worst("mv", True)
    change = max(errors["change"])
    return {
        "reference_update.moments": (
            plain[0] <= MOMENT_TOLERANCE
            and routed[0] <= ROUTED_MOMENT_TOLERANCE,
            "after %d AdamW steps the moments differ by at most %.2e rms "
            "of the reference's own outside the expert blocks (%s), "
            "tolerance %g, and %.2e inside them (%s), tolerance %g; median "
            "tensor %.2e (first moment), %.2e (second)"
            % (UPDATE_STEPS, plain[0], plain[1], MOMENT_TOLERANCE,
               routed[0], routed[1], ROUTED_MOMENT_TOLERANCE, median("m"),
               median("v"))),
        "reference_update.change": (
            change[0] <= CHANGE_TOLERANCE,
            "and the parameters' changes by at most %.2e (%s; median "
            "tensor %.2e), tolerance %g"
            % (change[0], change[1], median("change"), CHANGE_TOLERANCE))}


def _unit_stats(step):
    """A host copy of the step's per-class unit counters."""
    import copy
    return copy.deepcopy(getattr(step, "unit_stats", None) or {})


def _window_stats(before, after):
    """{class: {unit: {counter: after - before}}}."""
    out = {}
    for cls, units in after.items():
        out[cls] = {
            unit: {name: value - before.get(cls, {}).get(unit, {}).get(
                name, 0) for name, value in stats.items()}
            for unit, stats in units.items()}
    return out


def build(run):
    """(the CLI driver with the cell's workflow initialized, the
    generator's plan): set-up as a user's ``python -m veles_tpu
    <workflow.py> --mode scan`` makes it, stopped before the first
    epoch."""
    from veles_tpu.__main__ import Main
    from veles_tpu.backends import cache_root
    from veles_tpu.config import root
    # the one cache directory: $JAX_COMPILATION_CACHE_DIR when the machine
    # sets it, else the checkout's .cache/ (fixed path, part of the key)
    root.common.engine.compilation_cache_dir = cache_root()
    plan = run.generator.generate(run.mix, run.seed, run.chips)
    namespace = run.config["config_namespace"]
    overrides = ["%s.loader.seed=%d" % (namespace, run.seed),
                 "%s.loader.minibatch_size=%d" % (namespace,
                                                  plan["minibatch"])]
    overrides += ["%s.%s=%r" % (namespace, k, v)
                  for k, v in getattr(run, "config_overrides", {}).items()]
    main = Main([os.path.join(run.config_dir, "workflow.py")] + overrides
                + ["-a", run.backend, "--compute-dtype",
                   run.config["compute_dtype"], "--precision-level",
                   str(run.config["precision_level"]), "--dry-run", "init"]
                + plan["argv"])
    run.note("imports done, building the workflow")
    if main.run():
        raise RuntimeError("the CLI driver failed at --dry-run init")
    run.note("workflow initialized (data set and weights on the device)")
    return main, plan


def run(run):
    import jax
    from veles_tpu import loader as loader_mod
    tracing, mix = run.tracing, run.mix
    main, plan = build(run)
    wf = main.workflow
    step, clock = wf.fused_step, wf.epoch_clock
    tracing.annotate(step, "run", "train.epoch_dispatch")
    tracing.annotate(wf.decision, "run", "train.decision")
    tracing.annotate(clock, "run", "train.epoch_clock")
    sequences_per_epoch = int(wf.loader.class_lengths[loader_mod.TRAIN])
    sequence = int(wf.loader.original_data.shape[1])
    steps_per_epoch = int(math.ceil(sequences_per_epoch
                                    / plan["minibatch"]))
    x, y = _fixed_minibatch(wf, plan["minibatch"])
    loss_before, _ = _system_loss(wf, step._params_)
    run.note("fixed-minibatch loss before training read")

    warm = int(mix["warm_epochs"])
    state = {"t0": None, "t1": None, "epochs": 0, "compiles_at_t0": None}
    traced = tracing.TracedWindow() if run.trace else None

    def on_epoch(clock):
        n = len(clock.epoch_ends)
        if n < warm:
            return False
        if n == warm:
            run.note("warm epochs done: the window opens")
            state["t0"] = clock.epoch_ends[-1]
            state["compiles_at_t0"] = run.monitor.backend_compiles
            state["setup_compile_s"] = run.monitor.compile_seconds
            state["stats_at_t0"] = _unit_stats(step)
            if traced:
                traced.open()
            return False
        state["epochs"] = n - warm
        state["t1"] = clock.epoch_ends[-1]
        if traced:
            done = state["epochs"] >= int(mix["trace_epochs"])
            if done:
                traced.close()
            return done
        return state["t1"] - state["t0"] >= run.seconds

    clock.on_epoch = on_epoch
    main.launcher.run()
    jax.block_until_ready(step._params_)
    run.note("window closed")
    compiles_in_window = run.monitor.backend_compiles \
        - state["compiles_at_t0"]
    window = state["t1"] - state["t0"]
    sequences = state["epochs"] * sequences_per_epoch
    stats = _window_stats(state["stats_at_t0"], _unit_stats(step))

    # -- outside the window: correctness --------------------------------------
    memory_peak = run.memory_peak()     # the system's, not the reference's
    # room for the reference: the solver state (two moments a parameter)
    # and the forward units' copies of the weights go; the parameters stay
    _free(step._opt_)
    for fwd in step.forwards:
        _free(fwd.params)
    loss_after, _ = _system_loss(wf, step._params_)
    reference = Reference(run)
    checks = compare_outputs(reference, wf, step._params_, x, y, "trained")
    run.note("trained parameters compared with the reference")

    def probe():
        return probe_parameters(step._params_, run.seed)
    trained = jax.device_get(step._params_)     # parked on the host
    shardings = jax.tree.map(lambda p: p.sharding, step._params_)
    _free(step._params_)
    probed = probe()
    checks.update(compare_outputs(reference, wf, probed, x, y, "probe"))
    _, counted = _system_loss(wf, probed)
    _free(probed)
    run.note("probe parameters compared with the reference")
    checks.update(compare_updates(reference, wf, probe, x, y))
    run.note("AdamW steps compared with the reference")
    step._params_ = jax.device_put(trained, shardings)
    step._opt_ = [{name: gd.solver.init(p, jax.numpy)
                   for name, p in layer.items()}
                  for gd, layer in zip(step.gd_units, step._params_)]

    def total(units, counter):
        return sum(int(u[counter]) for u in units.values())
    rows = {cls: total(units, "moe_rows") for cls, units in stats.items()}
    routed = {cls: total(units, "moe_routed")
              for cls, units in stats.items()}
    probe_rows = total(counted["units"], "moe_rows")
    probe_routed = total(counted["units"], "moe_routed")
    checks["dropless"] = (
        rows == routed and probe_rows == probe_routed,
        "rows the grouped product computed / rows routed to held experts: "
        "window %s / %s, probe batch %d / %d"
        % (rows, routed, probe_rows, probe_routed))
    checks["loss_finite_and_lower"] = (
        math.isfinite(loss_after) and loss_after < loss_before,
        "fixed-minibatch loss %.6f -> %.6f" % (loss_before, loss_after))
    checks["no_compile_in_window"] = (
        compiles_in_window == 0, "%d compile(s) inside the window"
        % compiles_in_window)
    spread = {d for leaf in jax.tree.leaves(step._params_)
              for d in leaf.devices()}
    checks["parameters_on_every_chip"] = (
        len(spread) == run.chips and all(
            d.platform == run.backend for d in spread),
        "parameters on %d device(s), the cell asks for %d"
        % (len(spread), run.chips))
    main.launcher.stop()
    run.note("compared with the reference")

    run.counters.update(
        setup_compile_s=state["setup_compile_s"],
        train_steps=state["epochs"] * steps_per_epoch,
        epochs=state["epochs"], images=sequences,
        images_per_step=plan["minibatch"],
        tokens=sequences * sequence, tokens_per_s=sequences * sequence
        / window, sequence_length=sequence,
        valid_images=state["epochs"] * int(
            wf.loader.class_lengths[loader_mod.VALID]),
        valid_steps=state["epochs"] * int(math.ceil(
            wf.loader.class_lengths[loader_mod.VALID] / plan["minibatch"])),
        compile_cache_hits=run.monitor.cache_hits,
        compile_cache_misses=run.monitor.cache_misses)
    if rows:
        by_layer = {}
        for units in stats.values():
            for unit, u in units.items():
                by_layer[unit] = by_layer.get(unit, 0) + u["expert_tokens"]
        run.counters.update(
            moe_rows_train=rows.get("train", 0),
            moe_rows_valid=rows.get("validation", 0),
            expert_tokens={unit: [int(n) for n in tokens]
                           for unit, tokens in by_layer.items()},
            expert_load_max_over_mean=max(
                float(tokens.max() / max(tokens.mean(), 1e-30))
                for tokens in by_layer.values()))
    if traced:
        run.reduced = traced.reduce(run.chips)
        # the forty largest operation families of the traced window, in
        # seconds of self time: the breakdown's ten say too little of a
        # step with a dozen kinds of layer
        families = {}
        for events in run.reduced.devices.values():
            for family, ns in tracing.time_by_name(
                    events, tracing.op_family).items():
                families[family] = families.get(family, 0) + ns
        run.counters["device_family_s"] = {
            family: round(ns / 1e9, 4) for family, ns in sorted(
                families.items(), key=lambda kv: -kv[1])[:40]}
    return {
        "attempted": state["epochs"], "failed": 0,
        "memory_peak_bytes": memory_peak,
        "end_to_end": {"train_img_per_s": sequences / window,
                       "setup_s": state["t0"] - run.t_start},
        "checks": checks,
    }
