"""Mesh construction and sharding helpers.

The mental model follows the public scaling playbook: pick a mesh, annotate
shardings on params and batch, let XLA insert the collectives, profile,
iterate.  Axis conventions:

- ``data``   — batch (data parallelism; gradient psum over this axis)
- ``model``  — hidden/feature dims (tensor parallelism)
- ``seq``    — sequence dim (ring attention, parallel/ring.py)
- ``pipe``   — pipeline stages (GPipe schedule, parallel/pipeline.py)
- ``expert`` — MoE experts (switch routing, parallel/moe.py)

A mesh is laid out so ``data`` spans the slowest-varying device
dimension (DCN across slices in a real pod) and the ppermute-ring axes
(``model``, and especially ``seq``/``pipe`` whose hops are
neighbor-to-neighbor every tick) the fastest (ICI neighbors);
``expert`` sits between — its psum combine is bandwidth-bound but not
latency-critical.
"""

import numpy


def make_mesh(axes=None, devices=None):
    """Build a Mesh from ``{"axis": size}``; sizes must multiply to the
    device count (one axis may be -1 to absorb the remainder)."""
    import jax
    from jax.sharding import Mesh
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    axes = dict(axes or {"data": n})
    names, sizes = list(axes.keys()), list(axes.values())
    if -1 in sizes:
        known = int(numpy.prod([s for s in sizes if s != -1]))
        sizes[sizes.index(-1)] = n // known
    if int(numpy.prod(sizes)) != n:
        raise ValueError("mesh %s does not cover %d devices" %
                         (dict(zip(names, sizes)), n))
    dev_array = numpy.asarray(devices).reshape(sizes)
    return Mesh(dev_array, tuple(names))


def mesh_for_spec(spec, devices=None):
    """Rebuild a Mesh from a pickled :func:`mesh_spec` on THIS process.

    Unlike :func:`make_mesh` the spec need not cover every local device:
    the first ``prod(sizes)`` devices are taken, so a snapshot written
    on a small topology restores on a bigger host unchanged (and the
    caller may always assign a different Mesh before initialize for a
    true cross-mesh restore)."""
    import jax
    sizes = [int(s) for s in dict(spec).values()]
    if -1 in sizes:
        return make_mesh(spec, devices)
    n = int(numpy.prod(sizes))
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < n:
        raise ValueError("mesh %s needs %d devices; this process has %d"
                         % (dict(spec), n, len(devices)))
    return make_mesh(spec, devices[:n])


def mesh_spec(mesh):
    """Picklable ``{axis: size}`` geometry of a Mesh.  jax Device
    handles are process-local and cannot be pickled: snapshots store
    the spec (:func:`spec_in_state`) and the restoring process rebuilds
    the mesh over its own devices (:func:`live_mesh`)."""
    return {name: int(size) for name, size in mesh.shape.items()}


def spec_in_state(state):
    """``state`` (what a ``__getstate__`` is about to return) with its
    ``"mesh"`` as a spec: how every holder of a mesh (the workflow, the
    trainer step) pickles it."""
    mesh = state.get("mesh")
    if mesh is not None and not isinstance(mesh, dict):
        state["mesh"] = mesh_spec(mesh)
    return state


def live_mesh(mesh):
    """What a holder's ``initialize`` makes of its ``mesh``: a spec
    (restored from a snapshot) is rebuilt over this process's devices;
    a Mesh, or none, stays."""
    return mesh_for_spec(mesh) if isinstance(mesh, dict) else mesh


def register_mesh_metrics(mesh, workflow="-"):
    """Publish the mesh topology into the observability registry (one
    gauge series per axis) and stamp a ``mesh.initialized`` instant into
    the event log — a scrape of ``/metrics`` then says exactly what
    geometry a distributed step is running on."""
    from ..logger import events
    from ..observability.registry import REGISTRY
    g = REGISTRY.gauge("veles_mesh_axis_devices",
                       "Device-mesh axis sizes of the sharded step",
                       ("workflow", "axis"))
    for axis, size in mesh.shape.items():
        g.labels(workflow=workflow, axis=axis).set(int(size))
    REGISTRY.gauge("veles_mesh_devices_total",
                   "Total devices in the sharded step's mesh",
                   ("workflow",)).labels(workflow=workflow) \
        .set(int(numpy.prod(list(mesh.shape.values()))))
    events.event("mesh.initialized", workflow=workflow,
                 axes=dict(mesh.shape))


def batch_sharding(mesh, data_axis="data"):
    """Sharding for a [batch, ...] array: split the leading dim."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P(data_axis))


def replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P())


class TrainerPlacement:
    """Where the operands of a fused trainer lie on a mesh: the one
    holder of that rule, which ``FusedTrainStep`` and ``ScanEpochStep``
    keep when they are given a mesh (with none they keep no placement
    and make plain ``jax.jit`` calls).

    Operands are named by KIND: ``"param"`` (each parameter replicated,
    or split over ``model_axis`` as :func:`tensor_parallel_sharding`
    says), ``"opt"`` (every solver-state entry like its parameter:
    momentum buffers, adadelta tuples), ``"rep"`` (replicated: scalars,
    the metric accumulator, the resident data set, a scan's index
    tensors) and ``"batch"`` (a minibatch, leading dimension split over
    ``data_axis``).  XLA derives the collectives, the gradient
    all-reduce among them, from these annotations.

    Across processes every process holds the same full values (the
    loaders are identically seeded), and an array of one process cannot
    be resharded to a global sharding outside jit: operands are placed
    from HOST memory there (:meth:`place`, and the ``host_argnums`` of
    :meth:`jit`)."""

    def __init__(self, mesh, params, opt, data_axis="data",
                 model_axis=None, tp_mode="column", workflow="-"):
        import jax
        self.mesh = mesh
        self.multihost = jax.process_count() > 1
        if model_axis and model_axis in mesh.shape:
            param = tensor_parallel_sharding(mesh, params, model_axis,
                                             mode=tp_mode)
        else:
            param = data_parallel_sharding(mesh, params)
        self._kinds = {
            "param": param,
            "opt": [{name: (param[i][name],) * len(state)
                     if isinstance(state, tuple) else param[i][name]
                     for name, state in layer.items()}
                    for i, layer in enumerate(opt)],
            "rep": replicated(mesh),
            "batch": batch_sharding(mesh, data_axis)}
        register_mesh_metrics(mesh, workflow)

    def shardings(self, kinds):
        return tuple(self._kinds[kind] for kind in kinds)

    def place(self, operands, kind):
        """``operands`` (a pytree) on the sharding of ``kind``."""
        import jax
        if self.multihost:
            operands = jax.tree.map(numpy.asarray, operands)
        return jax.device_put(operands, self._kinds[kind])

    def jit(self, fn, in_kinds, out_kinds, donate_argnums,
            host_argnums=()):
        """``fn`` as one SPMD program: arguments and results laid out
        by kind.  Across processes the arguments ``host_argnums`` (a
        loader's minibatch, a scan's index tensors: values of this
        process, the same on every one) are first placed from host
        memory; in one process they go to the jit as they are."""
        import jax
        program = jax.jit(fn, in_shardings=self.shardings(in_kinds),
                          out_shardings=self.shardings(out_kinds),
                          donate_argnums=donate_argnums)
        if not (self.multihost and host_argnums):
            return program

        def placing(*args):
            args = list(args)
            for i in host_argnums:
                args[i] = self.place(args[i], in_kinds[i])
            return program(*args)
        return placing

    def constrain_batch(self, a):
        """Inside a program: ``a`` (a minibatch gathered from the
        replicated set) split over the data axis."""
        from jax import lax
        return lax.with_sharding_constraint(a, self._kinds["batch"])

    def fresh_accumulator(self, macc):
        """A fresh metric accumulator placed like the one the programs
        return.  Left on the default device it is a second signature of
        the jitted step (a second executable, compiled in the second
        epoch) and a reshard inside the dispatch at every class start.
        Across processes it stays as it is."""
        return macc if self.multihost else self.place(macc, "rep")

    def batch_staging(self):
        """The sharding a ``MinibatchPrefetcher`` stages minibatches
        onto ahead of the step; None across processes, where the step
        places host batches itself and nothing may be staged."""
        return None if self.multihost else self._kinds["batch"]


def data_parallel_sharding(mesh, params_tree):
    """Replicate every param (pure DP)."""
    import jax
    rep = replicated(mesh)
    return jax.tree.map(lambda _: rep, params_tree)


def tensor_parallel_sharding(mesh, params_tree, model_axis="model",
                             mode="column"):
    """Tensor parallelism over ``model``.

    ``mode="column"`` (default): every weight splits its *output* dim —
    2-D FC weights on dim 1, 4-D conv kernels (ky, kx, c_in, n_kernels)
    on the kernel dim 3 (each model-shard computes a slice of the output
    channels; XLA partitions the conv and gathers activations before the
    next layer — one collective per layer), 1-D biases on dim 0.

    ``mode="megatron"``: consecutive divisible 2-D FC weights ALTERNATE
    column (None, model) then row (model, None) splits — the Megatron
    MLP pairing.  A column layer's output stays feature-sharded, the
    following row layer consumes it shard-local, and only ONE psum (the
    row matmul's reduction) fires per pair instead of a gather per
    layer.  Row-split layers replicate their bias (it adds to a reduced,
    replicated activation); conv kernels keep the output-channel split.

    Everything indivisible replicates.  ``params_tree`` is the per-layer
    list of param dicts the fused trainers carry; megatron mode walks it
    in layer order to assign the alternation."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    import jax

    size = mesh.shape[model_axis]
    col2 = NamedSharding(mesh, P(None, model_axis))
    row2 = NamedSharding(mesh, P(model_axis, None))
    col1 = NamedSharding(mesh, P(model_axis))
    rep = NamedSharding(mesh, P())

    def base_spec(p):
        ndim = getattr(p, "ndim", 0)
        if ndim == 2 and p.shape[1] % size == 0:
            return col2
        if ndim == 4 and p.shape[3] % size == 0:
            return NamedSharding(mesh, P(None, None, None, model_axis))
        if ndim == 1 and p.shape[0] % size == 0:
            return col1
        return rep

    if mode not in ("column", "megatron"):
        raise ValueError("tp mode must be 'column' or 'megatron', got %r"
                         % (mode,))
    if mode == "column" or not isinstance(params_tree, (list, tuple)):
        return jax.tree.map(base_spec, params_tree)
    out = []
    want_row = False  # first eligible FC layer is column-split
    for layer in params_tree:
        if not isinstance(layer, dict):
            out.append(jax.tree.map(base_spec, layer))
            continue
        w = layer.get("weights")
        if getattr(w, "ndim", 0) != 2:
            # a non-FC layer (conv, paramless) breaks the pairing: its
            # output is not contracted-dim-sharded, so row-splitting the
            # next FC would only add resharding traffic
            want_row = False
        specs = {}
        if getattr(w, "ndim", 0) == 2 and want_row \
                and w.shape[0] % size == 0:
            specs["weights"] = row2
            # the row matmul's output is already reduced/replicated:
            # its bias must replicate too
            for name, p in layer.items():
                if name != "weights":
                    specs[name] = rep
            want_row = False
        else:
            for name, p in layer.items():
                specs[name] = base_spec(p)
            if getattr(w, "ndim", 0) == 2 and w.shape[1] % size == 0:
                want_row = True  # next divisible FC pairs as the row
        out.append(specs)
    return out
