"""The ``train`` driver: a training configuration through the program's
own command-line driver (``veles_tpu.__main__.Main`` and ``Launcher``),
for whole epochs over a window of the host clock.

Set-up: build and initialize the workflow from the configuration's
``workflow.py`` (data and weights from ``--seed``), read the loss of a
fixed minibatch, run ``warm_epochs`` epochs (the first compiles).  The
window opens at the end of the last warm epoch and closes at the end of
the first epoch that ends at or after ``--seconds``: one dispatch covers
a whole epoch, so the window is cut where work is whole and the rate is
all the images of the window over all its time.  With ``--trace 1`` the
window is ``trace_epochs`` epochs under the profiler instead.

After the window, outside it, on one fixed minibatch, against the
configuration's plain reference (which runs on the cell's first chip):
the system's evaluation-mode output under its final parameters; under
probe parameters (see :func:`probe_parameters`) its output, against the
reference at ``highest`` precision (the mathematics) and against the
reference in the arithmetic the configuration states; and ``UPDATE_STEPS``
steps of its own train step (forward, backward, weight decay, momentum;
dropout off) traced at ``highest`` precision against the reference's.
"""

import functools
import math
import os

#: System against the reference at ``highest`` precision: the root mean
#: square of the difference of the centred log-probabilities over their
#: spread.  The system multiplies float32 in ONE bf16 pass on the MXU
#: (precision level 0: operands rounded to 2^-9, summed in float32) and
#: the reference in six, so this covers that rounding: three and a half
#: times the 5.0-5.7e-3 measured on the chip (PERF.md, PR 23).  A wrong
#: weight (one convolution kernel 5 % off) lands outside: the rehearsal
#: test shows it.  It cannot tell float32 activations from bfloat16 ones,
#: which round the same operands; the next two can.
LOGIT_TOLERANCE = 2e-2
#: System against the reference in the STATED arithmetic (one bf16 pass,
#: float32 everywhere else).  Measured on the chip: 2.27e-3 and 2.28e-3
#: (two probe seeds, 2.16e-3 to 2.42e-3 over nine; the rest is probably
#: the system's LRN, which sums its window on the MXU and so rounds the
#: squares too), and with bfloat16
#: activations (``--compute-dtype bfloat16``) 5.67e-3 and 6.13e-3, which
#: must fail (PERF.md, PR 23).
STATED_LOGIT_TOLERANCE = 3.5e-3
#: The system's train step traced at ``highest`` precision against the
#: reference at ``highest``, per tensor: rms of the difference of the
#: parameter's change, and of the velocity, over the rms of the
#: reference's own; the worst tensor decides.  Measured on the chip over
#: seven seeds: 3.8e-3 to 1.3e-2 (the first convolution's kernel each
#: time: its gradient is what is left of 256 x 55 x 55 terms that
#: cancel, so the order of the float32 sums shows); the tolerance is
#: three times the largest.  A momentum of 0.8 for 0.9 moves
#: the velocities by 5 %, bfloat16 activations, which no precision
#: setting undoes, by 2e-1.  The step at precision level 0 is NOT held to
#: this: at the probe parameters the reference's own two-step update
#: moves by 7-20 % between one bf16 pass and six, so a comparison there
#: decides nothing a 5 % fault would show in (PERF.md, PR 23).
UPDATE_TOLERANCE = 4e-2
#: and the loss itself, a mean over 256 samples of the same quantities,
#: as a share of the loss (or absolute, where the loss is under 1)
LOSS_TOLERANCE = 2e-3
#: train steps of the update comparison: the second starts from a
#: velocity that is not zero, so the momentum term is in it
UPDATE_STEPS = 2


def _fixed_minibatch(wf, batch):
    """The first ``batch`` train samples and their labels."""
    import jax.numpy as jnp
    import numpy
    from veles_tpu import loader as loader_mod
    step, ld = wf.fused_step, wf.loader
    first = ld.class_end_offsets[loader_mod.VALID]
    idx = numpy.arange(first, first + batch, dtype=ld.INDEX_DTYPE)
    return (jnp.take(step._data_dev_, idx, axis=0),
            jnp.take(step._y_dev_, idx, axis=0))


def _system_eval(wf, params, x, y):
    """(loss, probabilities) of the step's own evaluation function under
    ``params``, dropout off."""
    import numpy
    step = wf.fused_step
    _, loss, out = step._eval_step_(params, step._macc_init(), x, y,
                                    numpy.int32(x.shape[0]))
    return float(loss), numpy.asarray(out)


def system_update(wf, params, x, y, steps):
    """(parameters, solver state) after ``steps`` calls of the step's own
    train function on the one minibatch, from ``params`` (consumed: the
    step donates them) and a fresh solver state.  It is the function the
    epoch scan runs per minibatch, jitted alone, traced at ``highest``
    precision (what ``--precision-level 2`` sets) and with no seed: a
    stochastic layer then takes its evaluation form, so the reference
    needs no copy of the program's dropout masks."""
    import jax
    import jax.numpy as jnp
    import numpy
    step = wf.fused_step
    opt = [{name: gd.solver.init(p, jnp) for name, p in layer.items()}
           for gd, layer in zip(step.gd_units, params)]
    macc = step._macc_init()
    with jax.default_matmul_precision("highest"):
        for _ in range(steps):
            params, opt, macc, _, _ = step._train_step_(
                params, opt, macc, x, y, numpy.int32(x.shape[0]), None,
                step.lr_scale)
    return params, opt


def probe_parameters(params, seed):
    """Parameters of the shapes and placement of ``params`` at a scale at
    which the signal survives every layer (He: normal, variance 2 /
    fan-in; biases normal 0.1).  Under the sample's own initialisation
    (standard deviation 0.01) the activations die out layer by layer and
    the logits are the last layer's bias whatever the other weights are,
    so a comparison there cannot see a wrong weight (PERF.md, PR 23); the
    system's evaluation function is therefore ALSO compared with the
    reference under these."""
    import jax
    import jax.numpy as jnp
    import numpy
    key = jax.random.key(int(seed) & 0x7FFFFFFF)
    out = []
    for i, layer in enumerate(params):
        new = {}
        for j, (name, p) in enumerate(sorted(layer.items())):
            k = jax.random.fold_in(jax.random.fold_in(key, i), j)
            scale = math.sqrt(2.0 / numpy.prod(p.shape[:-1])) \
                if name == "weights" else 0.1
            new[name] = jax.device_put(
                jax.random.normal(k, p.shape, jnp.float32) * scale,
                p.sharding)
        out.append(new)
    return out


def reference_weights(params):
    """The program's per-layer parameter dictionaries as the reference's
    ``(weights, bias)`` pairs."""
    return [(p["weights"], p["bias"]) for p in params if "weights" in p]


def relative_rms(got, want):
    """rms(got - want) over rms(want)."""
    import numpy
    got, want = numpy.asarray(got, numpy.float64), \
        numpy.asarray(want, numpy.float64)
    return float(numpy.sqrt(numpy.mean((got - want) ** 2))
                 / max(numpy.sqrt(numpy.mean(want ** 2)), 1e-30))


class Reference:
    """The configuration's plain reference as jitted functions of the
    weights, on the cell's first chip whatever the system's mesh (the
    same programs in every cell of the configuration)."""

    def __init__(self, run):
        import jax
        reference = run.config_module("reference")
        layers, solver = run.config["layers"], run.config["solver"]
        chip = run.devices[0]

        def outputs(precision, weights, x, y):
            logits = reference.forward(layers, weights, x, precision)
            return (reference.softmax_loss(logits, y),
                    jax.nn.log_softmax(logits))

        def update(weights, x, y):
            return reference.momentum_steps(layers, solver, weights, x, y,
                                            UPDATE_STEPS)

        def on_chip(function):
            jitted = jax.jit(function)
            return lambda *args: jitted(*jax.device_put(args, chip))
        self.outputs = {
            precision: on_chip(functools.partial(outputs, precision))
            for precision in ("highest", "default")}
        self.update = on_chip(update)


def compare_outputs(reference, params, x, y, loss, probabilities, tag,
                    stated=True):
    """{check: (ok, detail)} of the system's fixed-minibatch output
    against the plain reference given the same parameters; ``stated``:
    also against the reference in the stated arithmetic."""
    import numpy
    z_sys = numpy.log(numpy.maximum(probabilities, 1e-38))
    z_sys = z_sys - z_sys.mean(axis=1, keepdims=True)
    checks = {}
    against = [("highest", "reference", LOGIT_TOLERANCE)]
    if stated:
        against.append(("default", "stated_precision",
                        STATED_LOGIT_TOLERANCE))
    for precision, name, limit in against:
        ref_loss, z_ref = reference.outputs[precision](
            reference_weights(params), x, y)
        ref_loss, z_ref = float(ref_loss), numpy.asarray(z_ref)
        z_ref = z_ref - z_ref.mean(axis=1, keepdims=True)
        err = relative_rms(z_sys, z_ref)
        checks["%s_logits.%s" % (name, tag)] = (
            err <= limit,
            "centred log-probabilities differ by %.2e rms of their "
            "spread %.3g, tolerance %g" % (err, z_ref.std(), limit))
        if precision == "highest":
            tolerance = LOSS_TOLERANCE * max(1.0, abs(ref_loss))
            checks["reference_loss." + tag] = (
                abs(ref_loss - loss) <= tolerance,
                "system %.6f, reference %.6f, tolerance %.2g"
                % (loss, ref_loss, tolerance))
    return checks


def compare_updates(reference, wf, probe, x, y):
    """{check: (ok, detail)} of the system's own train steps from the
    probe parameters against the reference's, both at ``highest``
    precision: every parameter tensor's change and every velocity, the
    worst tensor deciding.  ``probe`` is a function that makes the
    parameters anew (the step consumes them)."""
    import numpy
    start = reference_weights(probe())
    params, opt = system_update(wf, probe(), x, y, UPDATE_STEPS)
    velocity = [(o["weights"][0], o["bias"][0]) for o in opt
                if "weights" in o]
    weights = reference_weights(params)
    ref_weights, ref_velocity = reference.update(start, x, y)
    errors = []
    for i in range(len(start)):
        for j, kind in enumerate(("weights", "bias")):
            before = numpy.asarray(start[i][j])
            errors.append((max(
                relative_rms(numpy.asarray(weights[i][j]) - before,
                             numpy.asarray(ref_weights[i][j]) - before),
                relative_rms(velocity[i][j], ref_velocity[i][j])),
                "%s %d" % (kind, i)))
    worst, where = max(errors)
    return {"reference_update.probe": (
        worst <= UPDATE_TOLERANCE,
        "after %d train steps at highest precision the changes and "
        "velocities differ by at most %.2e rms of the reference's own (%s; "
        "median tensor %.2e), tolerance %g"
        % (UPDATE_STEPS, worst, where, sorted(errors)[len(errors) // 2][0],
           UPDATE_TOLERANCE))}


def run(run):
    import jax
    from veles_tpu import loader as loader_mod
    from veles_tpu.__main__ import Main
    from veles_tpu.backends import cache_root
    from veles_tpu.config import root
    tracing, mix = run.tracing, run.mix
    # the one cache directory: $JAX_COMPILATION_CACHE_DIR when the machine
    # sets it, else the checkout's .cache/ (fixed path, part of the key)
    root.common.engine.compilation_cache_dir = cache_root()
    plan = run.generator.generate(mix, run.seed, run.chips)
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
    wf = main.workflow
    step, clock = wf.fused_step, wf.epoch_clock
    tracing.annotate(step, "run", "train.epoch_dispatch")
    tracing.annotate(wf.decision, "run", "train.decision")
    tracing.annotate(clock, "run", "train.epoch_clock")
    images_per_epoch = int(wf.loader.class_lengths[loader_mod.TRAIN])
    steps_per_epoch = int(math.ceil(images_per_epoch / plan["minibatch"]))
    x, y = _fixed_minibatch(wf, min(256, plan["minibatch"]))
    loss_before, _ = _system_eval(wf, step._params_, x, y)
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
    images = state["epochs"] * images_per_epoch

    # -- outside the window: correctness --------------------------------------
    memory_peak = run.memory_peak()     # the system's, not the reference's
    loss_after, probabilities = _system_eval(wf, step._params_, x, y)
    reference = Reference(run)
    # under the trained parameters the signal has died out (see
    # probe_parameters): a reading in the stated arithmetic decides nothing
    checks = compare_outputs(reference, step._params_, x, y, loss_after,
                             probabilities, "trained", stated=False)

    def probe():
        return probe_parameters(step._params_, run.seed)
    checks.update(compare_outputs(
        reference, probe(), x, y, *_system_eval(wf, probe(), x, y), "probe"))
    checks.update(compare_updates(reference, wf, probe, x, y))
    checks["loss_finite_and_lower"] = (
        math.isfinite(loss_after) and loss_after < loss_before,
        "fixed-minibatch loss %.6f -> %.6f" % (loss_before, loss_after))
    checks["no_compile_in_window"] = (
        compiles_in_window == 0, "%d compile(s) inside the window"
        % compiles_in_window)
    leaves = jax.tree_util.tree_leaves(step._params_)
    spread = {d for leaf in leaves for d in leaf.devices()}
    # data parallelism keeps a copy a chip: after the last all-reduce they
    # have to be the same numbers
    first = run.devices[0]
    apart = sum(
        not bool((jax.device_put(copy.data, first)
                  == leaf.addressable_shards[0].data).all())
        for leaf in leaves for copy in leaf.addressable_shards[1:]
        if copy.data.shape == leaf.shape)
    checks["parameters_on_every_chip"] = (
        len(spread) == run.chips and apart == 0 and all(
            d.platform == run.backend for d in spread),
        "parameters on %d device(s), the cell asks for %d; %d copies differ "
        "from the first chip's" % (len(spread), run.chips, apart))
    main.launcher.stop()
    run.note("compared with the reference")

    run.counters.update(
        setup_compile_s=state["setup_compile_s"],
        train_steps=state["epochs"] * steps_per_epoch,
        epochs=state["epochs"], images=images,
        images_per_step=plan["minibatch"],
        compile_cache_hits=run.monitor.cache_hits,
        compile_cache_misses=run.monitor.cache_misses)
    if traced:
        run.reduced = traced.reduce(run.chips)
    return {
        "attempted": state["epochs"], "failed": 0,
        "memory_peak_bytes": memory_peak,
        "end_to_end": {"train_img_per_s": images / window,
                       "setup_s": state["t0"] - run.t_start},
        "checks": checks,
    }
