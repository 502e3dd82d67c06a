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
    handles are process-local and cannot be pickled — snapshots store
    the spec and ``make_mesh(spec)`` rebuilds the mesh on the restoring
    process's devices (the sharded steps do this in initialize)."""
    return {name: int(size) for name, size in mesh.shape.items()}


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


def fresh_accumulator(step, macc):
    """A trainer's fresh metric accumulator ``macc``, placed like the one
    its jitted step returns (``step._rep_``, once the operands are
    placed).  Left on the default device it is a second signature of the
    jitted step — a second executable, compiled in the second epoch — and
    a reshard inside the dispatch at every class start.  Across
    processes a single-device array cannot be placed outside jit, so
    there it stays as it is."""
    import jax
    rep = getattr(step, "_rep_", None)
    if rep is None or jax.process_count() > 1:
        return macc
    return jax.device_put(macc, rep)


def trainer_shardings(mesh, params, opt, model_axis=None,
                      tp_mode="column"):
    """The fused trainers' operand shardings: params tensor-sharded over
    ``model_axis`` when given (else replicated DP), opt-state entries
    shaped like their param (momentum buffers, adadelta tuples), plus
    the replicated spec for scalars/metrics.  Shared by the per-step
    (parallel/dp.py) and epoch-scan (parallel/scan.py) mesh trainers."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    if model_axis and model_axis in mesh.shape:
        param_shard = tensor_parallel_sharding(mesh, params, model_axis,
                                               mode=tp_mode)
    else:
        param_shard = data_parallel_sharding(mesh, params)
    opt_shard = [
        {name: tuple(param_shard[i][name]
                     for _ in range(len(opt[i][name])))
         if isinstance(opt[i][name], tuple)
         else param_shard[i][name]
         for name in opt[i]}
        for i in range(len(opt))]
    return param_shard, opt_shard, NamedSharding(mesh, P())


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
