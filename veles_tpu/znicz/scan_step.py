"""ScanEpochStep: one XLA dispatch per dataset class via ``lax.scan``.

The fused per-minibatch step (fused.py) still pays one host→device dispatch
per minibatch, which dominates small models (its cost on the present chip:
not measured).  This unit collapses an ENTIRE class (all train minibatches, or all
validation minibatches) into one jitted ``lax.scan``:

    (params, opt, macc) = scan(body, init, (idx_matrix, sizes))

with the resident FullBatch dataset gathered per-iteration *inside* the
scan (``jnp.take``) in the source, masks built from the per-batch
``sizes`` vector, so results are bit-identical to the per-step path
(asserted in tests).  What the v5e's compiler makes of that gather is
another program: rows cannot be gathered from the device's default layout
of ``f32[8704, 227, 227, 3]`` (batch dimension minor-most), so each
dispatch first copies the WHOLE set to bfloat16, hoisted out of the loop
(13.5 ms a class a chip), and the iterations gather from the copy.  The
per-step trainer cured the same disease by placing the set where its
compiler asks (``FusedTrainStep._place_data``); in scan form that
placement still leaves a hoisted whole-set convert, so the scans are left
as they are (PERF.md section 6, PR 29; ROADMAP S12).  Host
work per class: build the index matrix (numpy), one dispatch (the index
matrix rides along as an argument), then the class-end epilogue
(``FusedTrainStep._finish_class``) enqueued behind the running scan —
sixteen weight copies for AlexNet, a fresh accumulator — and last the one
blocking read of the class's scalars.  Measured on the v5e: PERF.md
sections 5 and 6 (PR 27).

The unit replaces loader+fused_step in the control graph (repeater →
scan_step → decision); the Loader still owns the dataset, shuffling, and
epoch counters — this unit drives its flags so Decision units observe the
exact same protocol (SURVEY.md §7: partition units into traced and host).
"""

import numpy

from ..logger import events
from .. import loader as loader_mod
from .fused import FusedTrainStep, jit_program


class ScanEpochStep(FusedTrainStep):
    """FusedTrainStep that consumes one whole class per ``run()``.

    Over a ``mesh`` the resident set is REPLICATED (every shard gathers
    its own minibatch rows, then a sharding constraint splits the
    batch); a set too large to replicate goes through the per-step
    ``FusedTrainStep``, whose loader feeds the shards.  Across processes
    every process builds the same index tensors from its identically
    seeded loader (``tests/test_multihost.py``: two processes end
    bit-identical to each other and within 2e-5 of the one-process
    scan)."""

    DISPATCH = "one lax.scan dispatch per dataset class"
    DISPATCHES_STEPS = False

    def __init__(self, workflow, forwards, gd_units, loss="softmax",
                 **kwargs):
        super().__init__(workflow, forwards, gd_units, loss=loss, **kwargs)
        self.loader = None          # set by link_scan_loader
        self._class_cursor = 0
        self._epochs_done = 0

    def link_scan_loader(self, loader):
        self.loader = loader
        # keep the attribute links Decision peeks at coherent
        self.link_loader(loader)
        return self

    def initialize(self, device=None, **kwargs):
        if not self.loader.is_initialized:
            # normally the dependency walk has initialized the loader
            # already (it precedes this unit in the graph); this covers
            # hand-built workflows
            self.loader.initialize(device=device, **kwargs)
        super().initialize(device=device, **kwargs)
        import jax
        import jax.numpy as jnp
        from jax import lax

        train, evaluate = self._step_fns_
        self._hold_resident_set(self.loader)
        place = self._placement_

        def gathered(a, bidx):
            """A minibatch's rows of the resident ``a``; over a mesh,
            split over the data axis."""
            rows = jnp.take(a, bidx, axis=0)
            return rows if place is None else place.constrain_batch(rows)

        def train_scan(data_dev, y_dev, params, opt, macc, idx, sizes,
                       seeds, lr_scale):
            def body(carry, batch):
                p, o, m = carry
                bidx, bsize, bseed = batch
                with jax.named_scope("gather"):
                    x = gathered(data_dev, bidx)
                    y = gathered(y_dev, bidx)
                p, o, m, loss, _ = train(p, o, m, x, y, bsize, bseed,
                                         lr_scale)
                return (p, o, m), loss
            (params, opt, macc), losses = lax.scan(
                body, (params, opt, macc), (idx, sizes, seeds))
            return params, opt, macc, losses

        def eval_scan(data_dev, y_dev, params, macc, idx, sizes):
            def body(m, batch):
                bidx, bsize = batch
                with jax.named_scope("gather"):
                    x = gathered(data_dev, bidx)
                    y = gathered(y_dev, bidx)
                m, loss, _ = evaluate(params, m, x, y, bsize)
                return m, loss
            macc, losses = lax.scan(body, macc, (idx, sizes))
            return macc, losses

        # across processes the bulk index tensors are per-run host numpy,
        # identical on every process (argnums 5, 6, 7 / 4, 5)
        self._train_scan_ = jit_program(
            place, train_scan,
            ("rep", "rep", "param", "opt", "rep", "rep", "rep", "rep", "rep"),
            ("param", "opt", "rep", "rep"), donate_argnums=(2, 3, 4),
            host_argnums=(5, 6, 7))
        self._eval_scan_ = jit_program(
            place, eval_scan, ("rep", "rep", "param", "rep", "rep", "rep"),
            ("rep", "rep"), donate_argnums=(3,), host_argnums=(4, 5))

    def _next_seeds(self, n):
        """Deterministic consecutive per-batch seeds (matches the per-step
        path's counter increments), wrapped to int32 range."""
        seeds = (numpy.arange(self._seed_counter + 1,
                              self._seed_counter + 1 + n,
                              dtype=numpy.int64) % 0x7FFF0000).astype(
            numpy.int32)
        self._seed_counter = (self._seed_counter + n) % 0x7FFF0000
        return seeds

    # -- epoch driving -------------------------------------------------------
    def _classes_with_samples(self):
        return [c for c in (loader_mod.TEST, loader_mod.VALID,
                            loader_mod.TRAIN)
                if self.loader.class_lengths[c] > 0]

    def _class_index_matrix(self, cls):
        """(idx_matrix[nb, B], sizes[nb]) over the class's shuffled span."""
        ld = self.loader
        start = 0 if cls == loader_mod.TEST else ld.class_end_offsets[
            cls - 1]
        end = ld._class_end(cls)
        span = numpy.asarray(ld.shuffled_indices.map_read()[start:end])
        B = ld.max_minibatch_size
        nb = (len(span) + B - 1) // B
        idx = numpy.empty((nb, B), ld.INDEX_DTYPE)
        sizes = numpy.empty(nb, numpy.int32)
        for i in range(nb):
            chunk = span[i * B:(i + 1) * B]
            sizes[i] = len(chunk)
            idx[i, :len(chunk)] = chunk
            if len(chunk) < B:
                idx[i, len(chunk):] = chunk[0]  # pad; masked by sizes
        return idx, sizes

    def _dispatch(self, cls, idx, sizes):
        """Hand one class's index matrix to its jitted scan
        (``step.dispatch``: the argument hand-over and the enqueue; the
        device runs on) and file the loader's served count."""
        with events.timed("step.dispatch"):
            if cls == loader_mod.TRAIN:
                (self._params_, self._opt_, self._macc_, losses) = \
                    self._train_scan_(
                        self._data_dev_, self._y_dev_, self._params_,
                        self._opt_, self._macc_, idx, sizes,
                        self._next_seeds(len(sizes)), float(self.lr_scale))
            else:
                self._macc_, losses = self._eval_scan_(
                    self._data_dev_, self._y_dev_,
                    self._params_, self._macc_, idx, sizes)
        self.loss = losses[-1]
        self.loader.samples_served += int(sizes.sum())

    def run(self):
        ld = self.loader
        new_epoch = self._class_cursor == 0 and self._epochs_done > 0
        classes = self._classes_with_samples()
        cls = classes[self._class_cursor]
        epoch = ld.epoch_number + new_epoch
        events.set_work(epoch)
        with events.timed("step.run", cls=loader_mod.CLASS_NAME[cls],
                          epoch=epoch) as span:
            if new_epoch:
                # same moment the per-step loader wraps: entering a new
                # epoch
                ld.epoch_number += 1
                with events.timed("step.shuffle"):
                    ld.shuffle()
            with events.timed("step.index_matrix"):
                idx, sizes = self._class_index_matrix(cls)
            span.count(**self._work(len(sizes), int(sizes.sum())))
            self._dispatch(cls, idx, sizes)
            # drive the loader protocol so Decision sees normal class ends
            ld.minibatch_class = cls
            ld.minibatch_size = int(sizes[-1])
            last = self._class_cursor == len(classes) - 1
            self._class_cursor = 0 if last else self._class_cursor + 1
            ld.last_minibatch <<= True
            ld.train_ended <<= cls == loader_mod.TRAIN
            ld.valid_ended <<= cls == loader_mod.VALID
            ld.epoch_ended <<= last
            if last:
                self._epochs_done += 1
            self._finish_class()

    # -- bulk training -------------------------------------------------------
    def train_epochs(self, n_epochs):
        """Train ``n_epochs`` full TRAIN classes in ONE dispatch.

        Per-epoch shuffles are precomputed host-side and concatenated into
        one (n_epochs * nb, B) index tensor, so fixed-epoch bulk training
        (no per-epoch early stopping — the user trades Decision granularity
        for wall-clock) pays a single dispatch + a single metric read
        (what that buys on the present chip: not measured)."""
        ld = self.loader
        first = ld.epoch_number + (self._epochs_done > 0)
        events.set_work(first)
        with events.timed("step.run", cls=loader_mod.CLASS_NAME[
                loader_mod.TRAIN], epoch=first, epochs=n_epochs) as span:
            chunks = []
            for _ in range(n_epochs):
                if self._epochs_done:
                    ld.epoch_number += 1
                    with events.timed("step.shuffle"):
                        ld.shuffle()
                with events.timed("step.index_matrix"):
                    chunks.append(
                        self._class_index_matrix(loader_mod.TRAIN))
                self._epochs_done += 1
            idx = numpy.concatenate([c[0] for c in chunks])
            sizes = numpy.concatenate([c[1] for c in chunks])
            span.count(**self._work(len(sizes), int(sizes.sum())))
            self._dispatch(loader_mod.TRAIN, idx, sizes)
            ld.minibatch_class = loader_mod.TRAIN
            self._finish_class()
