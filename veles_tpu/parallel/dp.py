"""DistributedTrainStep: the fused train step sharded over a device mesh.

This is the TPU-native replacement for the reference's master–slave
data-parallel trainer (SURVEY.md §2.4): instead of slaves shipping pickled
gradients to a master over ZeroMQ (server.py:401-414), the batch is sharded
over the mesh's ``data`` axis, params are replicated (or sharded over
``model`` for tensor parallelism), and XLA inserts the gradient all-reduce
(psum over ICI) from the sharding annotations — the same jitted step, now
SPMD.

The synchronous all-reduce changes the *semantics* vs the reference's
asynchronous staleness-1 updates: every step sees the freshest weights,
which is strictly stronger; the reference's elastic join/leave semantics
move to checkpoint-restart (veles_tpu.distributed) because ICI collectives
are gang-scheduled (SURVEY.md §7 hard parts).
"""

from ..znicz.fused import FusedTrainStep
from . import mesh as mesh_mod


class DistributedTrainStep(FusedTrainStep):
    """FusedTrainStep over a Mesh: batch on ``data``, params replicated
    (optionally tensor-sharded over ``model``)."""

    def __init__(self, workflow, forwards, gd_units, mesh,
                 loss="softmax", data_axis="data", model_axis=None,
                 tp_mode="column", **kwargs):
        super().__init__(workflow, forwards, gd_units, loss=loss, **kwargs)
        self.mesh = mesh
        self.data_axis = data_axis
        self.model_axis = model_axis
        self.tp_mode = tp_mode

    def __getstate__(self):
        state = super().__getstate__()
        mesh = state.get("mesh")
        if mesh is not None and not isinstance(mesh, dict):
            # Device handles are process-local: snapshot the GEOMETRY
            # and rebuild over the restoring process's devices
            state["mesh"] = mesh_mod.mesh_spec(mesh)
        return state

    def _macc_init(self):
        return mesh_mod.fresh_accumulator(self, super()._macc_init())

    def make_trace(self):
        """Sharding survives tracing by construction: the SPMD step stays
        a natively-executed pre-compiled region, its in-program sharding
        annotations (and the ICI all-reduce XLA derives from them)
        untouched by the graph compiler."""
        from ..graphcomp.faces import OpaqueFace
        return OpaqueFace(self, "sharded fused step: one SPMD program "
                                "over the %r mesh axes"
                                % list(getattr(self.mesh, "axis_names",
                                               ())))

    def initialize(self, device=None, **kwargs):
        if isinstance(self.mesh, dict):   # restored from a snapshot
            self.mesh = mesh_mod.mesh_for_spec(self.mesh)
        super().initialize(device=device, **kwargs)
        import jax
        import numpy

        m = self.mesh
        multihost = jax.process_count() > 1
        if multihost:
            # cross-process placement accepts HOST data (every process
            # holds the same full value — loaders are identically
            # seeded); single-device jax.Arrays cannot be resharded to a
            # global sharding outside jit
            self._params_ = jax.tree.map(numpy.asarray, self._params_)
            self._opt_ = jax.tree.map(numpy.asarray, self._opt_)
            self._macc_ = jax.tree.map(numpy.asarray, self._macc_)
        param_shard, opt_shard, scalar = mesh_mod.trainer_shardings(
            m, self._params_, self._opt_, self.model_axis, self.tp_mode)
        self._rep_ = scalar     # where fresh accumulators go (_macc_init)
        batch_shard = mesh_mod.batch_sharding(m, self.data_axis)
        label_shard = batch_shard
        # input-pipeline hooks (loader/prefetch.py): single-host, the
        # prefetch worker device_puts minibatches straight onto the
        # batch sharding; multi-host, the step re-places host batches
        # itself below, so prefetch staging must stay off
        self._batch_sharding_ = None if multihost else batch_shard
        self._prefetch_unsupported_ = multihost
        mesh_mod.register_mesh_metrics(
            m, getattr(self._workflow, "name", "-"))

        self._params_ = jax.device_put(self._params_, param_shard)
        self._opt_ = jax.device_put(self._opt_, opt_shard)

        # re-jit the two steps with explicit shardings; XLA lowers the
        # gradient reduction to an ICI all-reduce.  ``size`` and ``seed``
        # stay DYNAMIC (replicated scalars) — a static size would trigger a
        # full recompile of the sharded step for every distinct tail-batch
        self._macc_ = jax.device_put(self._macc_, scalar)
        self._train_step_ = jax.jit(
            self._train_step_.__wrapped__,
            in_shardings=(param_shard, opt_shard, scalar, batch_shard,
                          label_shard, scalar, scalar, scalar),
            out_shardings=(param_shard, opt_shard, scalar, scalar,
                           batch_shard),
            donate_argnums=(0, 1, 2))
        self._eval_step_ = jax.jit(
            self._eval_step_.__wrapped__,
            in_shardings=(param_shard, scalar, batch_shard, label_shard,
                          scalar),
            out_shardings=(scalar, scalar, batch_shard),
            donate_argnums=(1,))
        if multihost:
            # multi-host: the per-step minibatch leaves the loader as a
            # process-local array; place it onto the global batch
            # sharding (same bytes on every process) before the SPMD call
            inner_train, inner_eval = self._train_step_, self._eval_step_

            def _global(x, shard):
                return jax.device_put(numpy.asarray(x), shard)

            def train_mh(params, opt, macc, x, y, size, seed, lr_scale):
                return inner_train(params, opt, macc,
                                   _global(x, batch_shard),
                                   _global(y, label_shard),
                                   size, seed, lr_scale)

            def eval_mh(params, macc, x, y, size):
                return inner_eval(params, macc, _global(x, batch_shard),
                                  _global(y, label_shard), size)

            self._train_step_ = train_mh
            self._eval_step_ = eval_mh
