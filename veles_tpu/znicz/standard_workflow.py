"""StandardWorkflow: config-driven NN training topology builder.

Re-creation of ``veles.znicz.standard_workflow.StandardWorkflow`` (absent;
documented at /root/reference/docs/source/
manualrst_veles_workflow_creation.rst:101-146): builds
repeater → loader → forwards[] → evaluator → decision → gds[] (reverse) →
loop from a ``layers`` config list, each entry
``{"type": <MAPPING>, "->": {forward kwargs}, "<-": {gd kwargs}}`` (flat
kwargs are accepted too and routed by prefix knowledge).

Two execution modes:

- **fused** (default on a real device): forwards trace into ONE jitted,
  donated train-step (:class:`FusedTrainStep`); the graph carries only the
  host-side control units (loader → fused → decision).  This is the
  TPU-idiomatic hot loop (SURVEY.md §7).
- **graph**: the classic per-unit chain with explicit GD units — the
  parity/debug path, and the shape the reference actually executes.

Both modes share the same forward units, weights, and decision logic, so a
workflow can be built fused for speed and inspected per-unit.
"""

from ..plumbing import Repeater
from ..registry import UnitRegistry
from ..workflow import Workflow
from .nn_units import ForwardBase, GradientDescentBase
from .all2all import All2AllSoftmax
from .evaluator import EvaluatorSoftmax, EvaluatorMSE
from .decision import DecisionGD, DecisionMSE
from .fused import FusedTrainStep


def parse_mcdnnic_topology(topology, parameters=None):
    """MCDNN string notation → a ``layers`` config list.

    The reference accepted topologies "like in the AlexNet paper"
    (manualrst_veles_workflow_creation.rst:41-47, used by the Lines
    sample): dash-separated tokens, e.g. ``12x256x256-32C5-MP2-64C5-
    MP2-1024N-10N``:

    - ``CxHxW`` (first token, optional) — the input spec, informational;
    - ``<n>C<k>`` — conv, n kernels of k x k (strict-ReLU);
    - ``MP<k>`` / ``AP<k>`` — max/avg pooling k x k, stride k;
    - ``<n>N`` — fully-connected with n neurons; tanh for hidden
      layers, softmax for the final one.

    ``parameters`` ({"->": {...}, "<-": {...}} or flat) seeds every
    generated layer's config (the reference's ``mcdnnic_parameters``)."""
    import re
    params = dict(parameters or {})
    fwd_base = dict(params.get("->", {}))
    gd_base = dict(params.get("<-", {}))
    flat = {k: v for k, v in params.items() if k not in ("->", "<-")}
    tokens = [t for t in str(topology).split("-") if t]
    if tokens and re.fullmatch(r"\d+(x\d+)+", tokens[0]):
        tokens = tokens[1:]  # input spec: shapes come from the loader
    layers = []
    for i, tok in enumerate(tokens):
        last = i == len(tokens) - 1
        m = re.fullmatch(r"(\d+)C(\d+)", tok)
        if m:
            n, k = int(m.group(1)), int(m.group(2))
            layers.append({"type": "conv_str",
                           "->": {"n_kernels": n, "kx": k, "ky": k,
                                  **fwd_base},
                           "<-": dict(gd_base), **flat})
            continue
        m = re.fullmatch(r"(M|A)P(\d+)", tok)
        if m:
            k = int(m.group(2))
            layers.append({"type": ("max_pooling" if m.group(1) == "M"
                                    else "avg_pooling"),
                           "->": {"kx": k, "ky": k, "sliding": (k, k)}})
            continue
        m = re.fullmatch(r"(\d+)N", tok)
        if m:
            n = int(m.group(1))
            layers.append({"type": "softmax" if last else "all2all_tanh",
                           "->": {"output_sample_shape": n, **fwd_base},
                           "<-": dict(gd_base), **flat})
            continue
        raise ValueError(
            "unrecognized mcdnnic token %r in %r (expected <n>C<k>, "
            "MP<k>/AP<k>, <n>N or an CxHxW input spec)"
            % (tok, topology))
    if not layers:
        raise ValueError("mcdnnic_topology %r has no layers" % topology)
    return layers


def _find_pair(type_name):
    """Resolve a layer-type MAPPING to its (forward, gd) classes via the
    unit registry (the reference resolves through its own MAPPING registry,
    manualrst_veles_workflow_parameters.rst:469)."""
    fwd = gd = None
    for cls in UnitRegistry.units.values():
        if getattr(cls, "MAPPING", None) != type_name:
            continue
        if issubclass(cls, ForwardBase):
            fwd = cls
        elif issubclass(cls, GradientDescentBase):
            gd = cls
    if fwd is None:
        raise ValueError("unknown layer type %r" % type_name)
    return fwd, gd


class StandardWorkflow(Workflow):
    """repeater → loader → forwards → evaluator → decision → gds → loop."""

    hide_from_registry = True

    def __init__(self, workflow=None, **kwargs):
        super().__init__(workflow, **kwargs)
        if kwargs.get("mcdnnic_topology"):
            if kwargs.get("layers"):
                raise ValueError(
                    "pass layers= OR mcdnnic_topology=, not both")
            self.layers_config = parse_mcdnnic_topology(
                kwargs["mcdnnic_topology"],
                kwargs.get("mcdnnic_parameters"))
        else:
            self.layers_config = list(kwargs.get("layers", ()))
        self.loss_function = kwargs.get("loss_function", "softmax")
        self.fused = kwargs.get("fused", True)
        # whole-workflow compilation (veles_tpu.graphcomp): None =
        # follow root.common.engine.graph_compile (default off)
        self.graph_compile = kwargs.get("graph_compile", None)
        self.mesh = kwargs.get("mesh")           # jax.sharding.Mesh → SPMD
        self.model_axis = kwargs.get("model_axis")
        self.tp_mode = kwargs.get("tp_mode", "column")
        # epoch_scan: one lax.scan dispatch per class instead of one
        # dispatch per minibatch (FullBatch loaders only)
        self.epoch_scan = kwargs.get("epoch_scan", False)
        self.decision_config = dict(kwargs.get("decision", {}))
        self.loader_config = dict(kwargs.get("loader", {}))
        # async input pipeline lookahead for the per-step path; None =
        # follow root.common.loader.prefetch_depth (default 2, 0 = sync)
        self.prefetch_depth = self.loader_config.pop("prefetch_depth",
                                                     None)
        self.trainer_config = dict(kwargs.get("trainer", {}))
        self.snapshotter_config = kwargs.get("snapshotter")  # dict|None
        self.snapshotter = None
        self.web_status = kwargs.get("web_status", False)
        self.status_reporter = None
        loader_factory = kwargs.get("loader_factory")
        if loader_factory is None:
            raise ValueError("StandardWorkflow requires loader_factory")
        self.repeater = Repeater(self)
        self.loader = loader_factory(self, **self.loader_config)
        self.forwards = []
        self.gds = []
        self.fused_step = None
        self.evaluator = None
        self.decision = None
        self._build()

    # -- construction --------------------------------------------------------
    def _split_layer_config(self, cfg):
        cfg = dict(cfg)
        type_name = cfg.pop("type")
        fwd_kwargs = dict(cfg.pop("->", {}))
        gd_kwargs = dict(cfg.pop("<-", {}))
        # flat keys: route the known GD hyperparameters, rest to forward
        gd_keys = {"learning_rate", "learning_rate_bias", "weights_decay",
                   "weights_decay_bias", "l1_vs_l2", "l1_vs_l2_bias",
                   "gradient_moment", "solver", "solver_parameters",
                   "factor_ortho"}
        for k, v in cfg.items():
            (gd_kwargs if k in gd_keys else fwd_kwargs).setdefault(k, v)
        return type_name, fwd_kwargs, gd_kwargs

    def _build(self):
        self.repeater.link_from(self.start_point)
        self.loader.link_from(self.repeater)

        prev = self.loader
        gd_pairs = []
        for cfg in self.layers_config:
            type_name, fwd_kwargs, gd_kwargs = self._split_layer_config(cfg)
            fwd_cls, gd_cls = _find_pair(type_name)
            fwd = fwd_cls(self, **fwd_kwargs)
            fwd.link_from(prev)
            if prev is self.loader:
                fwd.link_attrs(self.loader, ("input", "minibatch_data"))
            else:
                fwd.link_attrs(prev, ("input", "output"))
            if fwd.stochastic:
                # stochastic units draw per-train-minibatch keys in graph
                # mode; they watch the loader's class to know when
                fwd.link_attrs(self.loader, "minibatch_class")
            self.forwards.append(fwd)
            gd_pairs.append((gd_cls, gd_kwargs))
            prev = fwd

        # evaluator (graph mode only — fused mode computes the loss and
        # metrics inside the step) + decision
        if self.loss_function == "token":
            # next-token loss over [B, S] labels: the head computes it
            # inside the fused step (znicz/transformer.py); n_err counts
            # wrong tokens
            if not self.fused:
                raise ValueError("loss_function='token' needs the fused "
                                 "or the epoch-scan trainer")
            self.decision = DecisionGD(self, **self.decision_config)
        elif self.loss_function == "softmax":
            if not self.fused:
                self.evaluator = EvaluatorSoftmax(self)
            self.decision = DecisionGD(self, **self.decision_config)
        else:
            if not self.fused:
                self.evaluator = EvaluatorMSE(self)
            self.decision = DecisionMSE(self, **self.decision_config)

        # instantiate GD units (shared by both modes: they own solver state
        # and hyperparameters; fused mode reads them, graph mode runs them)
        from .nn_units import GenericVJPBackward, ParamlessForward
        for (gd_cls, gd_kwargs), fwd in zip(gd_pairs, self.forwards):
            if gd_cls is None:
                if not isinstance(fwd, ParamlessForward):
                    raise ValueError(
                        "no GD unit registered for parameterized layer %r"
                        % type(fwd).MAPPING)
                gd_cls = GenericVJPBackward  # paramless structural layer
            gd = gd_cls(self, **gd_kwargs)
            gd.link_forward(fwd)
            self.gds.append(gd)

        if self.snapshotter_config is not None:
            cfg = dict(self.snapshotter_config)
            fmt = cfg.pop("format", None)
            if fmt is None:
                from ..config import root
                fmt = root.common.snapshot.get("format", "pickle")
            if fmt in ("shards", "sharded"):
                from ..checkpoint import SnapshotterToShards as snap_cls
            elif fmt in ("pickle", "file", None):
                from ..snapshotter import SnapshotterToFile as snap_cls
            else:
                from ..registry import MappedObjectsRegistry
                snap_cls = MappedObjectsRegistry.get("snapshotter", fmt)
            self.snapshotter = snap_cls(self, **cfg)
            self.snapshotter.link_decision(self.decision)
            # snapshot the moment validation improves — BEFORE the next
            # train pass mutates the weights — so a restored
            # ``validation_X`` snapshot really is the model that scored X;
            # without the valid_ended conjunct every train-minibatch pass
            # after an improvement would snapshot again
            self.snapshotter.skip = ~(self.decision.improved &
                                      self.loader.valid_ended)

        if self.web_status:
            # heartbeat side-branch: fires off the decision each epoch,
            # does not gate the training loop
            from ..web_status import StatusReporter
            cfg = self.web_status if isinstance(self.web_status, dict) \
                else {}
            self.status_reporter = StatusReporter(self, **cfg)
            self.status_reporter.link_from(self.decision)
            self.status_reporter.link_loader(self.loader)

        if self.fused:
            self._build_fused()
        else:
            self._build_graph()
        self.repeater.gate_block = self.decision.complete
        self.end_point.gate_block = ~self.decision.complete

    def _build_fused(self):
        # forwards/gds stay OUT of the control graph: FusedTrainStep traces
        # through them
        for fwd in self.forwards:
            fwd.unlink_all()
        from .misc_units import ZeroFiller
        for fwd in self.forwards:
            if isinstance(fwd, ZeroFiller):
                raise ValueError(
                    "zero_filler is graph-mode only; use Conv(grouping=N) "
                    "in fused workflows (see ZeroFiller docstring)")
        # how many minibatches a dispatch covers is the class; where the
        # operands lie (one device, or --mesh) is the step's argument
        if self.epoch_scan:
            from .scan_step import ScanEpochStep as step_class
        else:
            step_class = FusedTrainStep
        self.fused_step = step_class(
            self, self.forwards, self.gds, loss=self.loss_function,
            mesh=self.mesh, model_axis=self.model_axis,
            tp_mode=self.tp_mode, **self.trainer_config)
        if self.epoch_scan:
            from ..mutable import Bool
            # the scan step drives the loader itself; the loader stays
            # linked (so it initializes before the scan step in dependency
            # order) but permanently blocked from running
            self.loader.gate_block = Bool(True)
            self.fused_step.link_from(self.repeater)
            self.fused_step.link_scan_loader(self.loader)
        else:
            self.fused_step.link_from(self.loader)
            self.fused_step.link_loader(self.loader)
            from ..loader.fullbatch import FullBatchLoader
            if self.mesh is None and isinstance(self.loader,
                                                FullBatchLoader):
                # HBM-resident dataset: gather rides inside the jitted
                # step — one executable launch per minibatch (over a
                # mesh the loader hands minibatches over: ROADMAP W5)
                self.fused_step.link_fused_gather(self.loader)
        self.decision.link_from(self.fused_step)
        self.decision.link_loader(self.loader)
        self.decision.link_evaluator(self.fused_step)
        tail = self._link_snapshotter(self.decision)
        self.repeater.link_from(tail)
        self.end_point.link_from(tail)

    def _build_graph(self):
        last_fwd = self.forwards[-1]
        self.evaluator.link_from(last_fwd)
        self.evaluator.link_attrs(last_fwd, "output")
        if isinstance(last_fwd, All2AllSoftmax):
            self.evaluator.link_attrs(last_fwd, "max_idx")
        if self.loss_function == "softmax":
            self.evaluator.link_attrs(
                self.loader, ("labels", "minibatch_labels"),
                ("batch_size", "minibatch_size"))
        else:
            self.evaluator.link_attrs(
                self.loader, ("target", "minibatch_targets"),
                ("batch_size", "minibatch_size"))
        self.decision.link_from(self.evaluator)
        self.decision.link_loader(self.loader)
        self.decision.link_evaluator(self.evaluator)

        prev = self._link_snapshotter(self.decision)
        train_gate = self.make_train_gate(self.loader)
        for i in reversed(range(len(self.forwards))):
            gd = self.gds[i]
            gd.link_from(prev)
            gd.link_attrs(self.loader, ("batch_size", "minibatch_size"))
            if i == len(self.forwards) - 1:
                gd.link_attrs(self.evaluator, "err_output")
            else:
                gd.link_attrs(self.gds[i + 1], ("err_output", "err_input"))
            if i == 0:
                gd.need_err_input = False  # nothing below to backprop into
            gd.gate_skip = train_gate
            prev = gd
        self.repeater.link_from(prev)
        self.end_point.link_from(prev)

    def _link_snapshotter(self, tail):
        if self.snapshotter is None:
            return tail
        self.snapshotter.link_from(tail)
        return self.snapshotter

    def __getstate__(self):
        from ..parallel.mesh import spec_in_state
        return spec_in_state(super().__getstate__())

    def initialize(self, device=None, **kwargs):
        from ..parallel.mesh import live_mesh
        self.mesh = live_mesh(self.mesh)
        # cross-mesh restore: the workflow's mesh (rebuilt from its spec
        # above, or a Mesh the caller assigned before initialize)
        # overrides the geometry the step snapshotted for itself
        step = getattr(self, "fused_step", None)
        if self.mesh is not None and getattr(step, "mesh", None) is not None:
            step.mesh = self.mesh
        if self.restored_from_snapshot:
            self._relink_gates()
        result = super().initialize(device=device, **kwargs)
        self._maybe_attach_prefetcher(device)
        self._maybe_attach_graph_compiler()
        return result

    def _maybe_attach_graph_compiler(self):
        """Adopt whole-workflow compilation behind the
        ``root.common.engine.graph_compile`` knob (or the per-workflow
        ``graph_compile=`` ctor override).  In graph mode the per-unit
        chain traces into one compiled program per minibatch; in fused/
        scan/mesh modes the pre-fused step passes through as its own
        region, so flipping the knob never regresses the blessed path.
        getattr: snapshots written before the knob existed restore."""
        from ..config import root
        enabled = getattr(self, "graph_compile", None)
        if enabled is None:
            enabled = root.common.engine.get("graph_compile", False)
        if enabled:
            self.attach_graph_compiler()

    def _maybe_attach_prefetcher(self, device):
        """Overlap host minibatch prep with device compute on the
        per-step fused path (loader/prefetch.py).  The epoch-scan path
        already amortizes the whole class into one dispatch, and a mesh
        step across processes places host batches itself, so both
        skip."""
        if not self.fused or self.epoch_scan or self.fused_step is None:
            return
        placement = getattr(self.fused_step, "_placement_", None)
        if placement is not None and placement.batch_staging() is None:
            return
        stage = bool(device is not None and
                     getattr(device, "exists", False))
        # getattr: snapshots written before the knob existed must still
        # restore (None = follow the global config default)
        self.attach_prefetcher(loader=self.loader,
                               depth=getattr(self, "prefetch_depth",
                                             None),
                               stage_to_device=stage)

    def _relink_gates(self):
        """Derived Bool expressions flatten to constants on pickle; rebuild
        them from the live Decision/loader after a restore."""
        from ..mutable import Bool
        self.repeater.gate_block = self.decision.complete
        self.end_point.gate_block = ~self.decision.complete
        self.decision.complete <<= False
        if self.snapshotter is not None:
            self.snapshotter.skip = ~(self.decision.improved &
                                      self.loader.valid_ended)
        if self.epoch_scan:
            self.loader.gate_block = Bool(True)
        if not self.fused:
            train_gate = self.make_train_gate(self.loader)
            for gd in self.gds:
                gd.gate_skip = train_gate

    def run(self):
        result = super().run()
        if self.fused_step is not None:
            self.fused_step.sync_weights()
        return result
