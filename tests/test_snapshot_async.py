"""Zero-stall asynchronous snapshotting (ISSUE 4): the capture/write
split must be invisible to everything that consumes snapshots — files
appear complete and atomic, restore parity with the synchronous path is
exact — while the writer honors the lifecycle contract: periodic-shot
coalescing (never improvements), exceptions re-raised on the next
``run()``, flush+join at workflow finish with no leaked threads.
"""

import glob
import os
import threading
import time

import numpy
import pytest

from veles_tpu.backends import Device
from veles_tpu.config import root
from veles_tpu.mutable import Bool
from veles_tpu.prng import RandomGenerator
from veles_tpu.snapshotter import (SnapshotterToDB, SnapshotterToFile,
                                   SnapshotWriter, restore)
from veles_tpu.znicz.standard_workflow import StandardWorkflow

from test_standard_workflow import BlobLoader, LAYERS


def _writer_threads():
    """Live snapshot-writer threads (same snapshot-set convention as
    test_prefetch._prefetch_threads — earlier tests' abandoned idle
    writers may await GC)."""
    return {t for t in threading.enumerate()
            if t.name.startswith("veles-snapwriter")}


def build(max_epochs, tmp_path=None, seed=31, snap_kwargs=None,
          minibatch=25, **wf_kwargs):
    import veles_tpu.prng.random_generator as rg
    rg._generators.clear()
    rg.get(0).seed(seed)
    if tmp_path is not None:
        cfg = {"prefix": "blob", "directory": str(tmp_path),
               "time_interval": 0, "compression": "gz"}
        cfg.update(snap_kwargs or {})
        wf_kwargs["snapshotter"] = cfg
    wf = StandardWorkflow(
        None, name="snapwf",
        loader_factory=BlobLoader,
        loader={"minibatch_size": minibatch,
                "prng": RandomGenerator().seed(5)},
        layers=LAYERS, loss_function="softmax",
        decision={"max_epochs": max_epochs, "silent": True},
        fused=True, **wf_kwargs)
    wf.initialize(device=Device(backend="cpu"))
    return wf


def test_finish_flushes_writer_files_complete_no_leaked_threads(tmp_path):
    before = _writer_threads()
    wf = build(3, tmp_path)           # async_write defaults ON
    assert wf.snapshotter._async_enabled()
    wf.run()
    # finish flushed + joined the writer: every file durable, no orphans
    assert _writer_threads() <= before
    snaps = glob.glob(str(tmp_path / "blob*.pickle.gz"))
    assert snaps, "no snapshot written"
    assert not glob.glob(str(tmp_path / "*.tmp"))
    current = str(tmp_path / "blob_current")
    assert os.path.islink(current)
    restored = restore(current)
    assert restored.restored_from_snapshot
    w = wf.snapshotter._writer_
    assert w._thread is None          # joined, restartable
    assert wf.snapshotter.writer_stats()["queued"] == 0
    assert wf.snapshotter.stall_s > 0


def test_async_off_is_synchronous(tmp_path):
    wf = build(2, tmp_path, snap_kwargs={"async_write": False})
    snap = wf.snapshotter
    snap.skip = Bool(False)
    snap.time_interval = 0
    snap.run()
    # the file is durable the moment run() returns; no writer exists
    assert os.path.exists(snap.destination)
    assert getattr(snap, "_writer_", None) is None
    restore(snap.destination)


def test_async_restore_parity_with_sync(tmp_path):
    """Acceptance: a restored async-written snapshot resumes training
    with metrics identical to a sync-written one."""
    results = {}
    for mode in (False, True):
        sub = tmp_path / ("async" if mode else "sync")
        sub.mkdir()
        wf = build(3, sub, snap_kwargs={"async_write": mode})
        wf.run()
        resumed = restore(str(sub / "blob_current"))
        resumed.decision.max_epochs = 6
        resumed.initialize(device=Device(backend="cpu"))
        resumed.run()
        results[mode] = (
            resumed.loader.epoch_number,
            resumed.decision.epoch_n_err_pt[1],
            [numpy.array(f.weights.map_read()) for f in resumed.forwards])
    assert results[True][0] == results[False][0]
    assert results[True][1] == pytest.approx(results[False][1], abs=1e-9)
    for wa, ws in zip(results[True][2], results[False][2]):
        numpy.testing.assert_allclose(wa, ws, atol=1e-7)


def test_writer_failure_reraises_on_next_run(tmp_path):
    wf = build(2, tmp_path)
    snap = wf.snapshotter
    snap.skip = Bool(False)
    snap.time_interval = 0

    def boom(obj, path):
        raise OSError("disk on fire")

    snap._write_file = boom
    snap.run()                       # submits; the writer hits boom
    deadline = time.monotonic() + 10
    while snap._writer_._failure is None:
        assert time.monotonic() < deadline, "writer never failed"
        time.sleep(0.01)
    with pytest.raises(OSError, match="disk on fire"):
        snap.run()
    # the failure was delivered exactly once — a further run proceeds
    del snap._write_file
    snap.run()
    assert snap.flush()


def test_coalescing_drops_oldest_periodic_never_improvements():
    w = SnapshotWriter(name="coalesce-test")
    gate = threading.Event()
    started = threading.Event()
    done = []

    def job(tag):
        def fn():
            started.set()
            assert gate.wait(10)
            done.append(tag)
        return fn

    w.submit(job("head"), improved=False)
    assert started.wait(10)           # writer busy on "head"
    w.submit(job("p1"), improved=False)
    w.submit(job("p2"), improved=False)   # coalesces p1 (drop-oldest)
    w.submit(job("i1"), improved=True)
    w.submit(job("i2"), improved=True)
    w.submit(job("p3"), improved=False)   # coalesces p2
    assert w.coalesced == 2
    gate.set()
    assert w.flush(timeout=10)
    assert done == ["head", "i1", "i2", "p3"]
    assert w.stats()["written"] == 4
    w.stop()


def test_queue_depth_is_bounded_for_periodic_shots():
    w = SnapshotWriter(name="depth-test")
    gate = threading.Event()
    started = threading.Event()

    def fn():
        started.set()
        gate.wait(10)

    w.submit(fn, improved=False)
    assert started.wait(10)
    for _ in range(50):
        w.submit(fn, improved=False)
    assert w.stats()["queued"] == 1   # depth-1: newest periodic only
    assert w.coalesced == 49
    gate.set()
    assert w.flush(timeout=10)
    w.stop()


def test_roundtrip_with_prefetcher_and_distributed_step(tmp_path):
    """Satellite: snapshot→restore under the PR 3 machinery — a
    MinibatchPrefetcher attached AND a FusedTrainStep over a mesh (dp)
    initialized.  The transient_-dropping __getstate__ must keep both
    out of the pickle, and resumed training must match an uninterrupted
    run (same minibatch walk ⇒ same weights and epoch metrics)."""
    import jax
    from veles_tpu.znicz.fused import FusedTrainStep
    from veles_tpu.parallel.mesh import make_mesh
    if len(jax.devices()) < 8:
        pytest.skip("needs the conftest 8-device virtual CPU mesh")

    ref = build(6, minibatch=40, mesh=make_mesh({"data": 8}))
    ref.run()

    part = build(3, tmp_path, minibatch=40, mesh=make_mesh({"data": 8}))
    assert type(part.fused_step) is FusedTrainStep
    assert part.fused_step.mesh is not None
    assert part.loader.prefetcher_ is not None
    part.run()

    resumed = restore(str(tmp_path / "blob_current"))
    # regression lock: the pickle carried neither the prefetch wrappers
    # nor a worker — the loader is back on its class-level run()
    assert "run" not in resumed.loader.__dict__
    assert getattr(resumed.loader, "prefetcher_", None) is None
    resumed.decision.max_epochs = 6
    resumed.initialize(device=Device(backend="cpu"))
    assert resumed.loader.prefetcher_ is not None   # re-attached live
    resumed.run()

    assert resumed.loader.epoch_number == ref.loader.epoch_number
    for fr, fu in zip(resumed.forwards, ref.forwards):
        assert numpy.allclose(fr.weights.map_read(),
                              fu.weights.map_read(),
                              atol=2e-5), type(fr).__name__
    assert resumed.decision.epoch_n_err_pt[1] == \
        pytest.approx(ref.decision.epoch_n_err_pt[1], abs=1e-9)


def test_db_snapshotter_async_roundtrip(tmp_path):
    wf = build(2)
    db = str(tmp_path / "snaps.sqlite3")
    snap = SnapshotterToDB(wf, prefix="blob", database=db,
                           time_interval=0)
    snap.skip = Bool(False)
    try:
        snap.run()
        assert snap._async_enabled()
        assert snap.flush()
        restored = SnapshotterToDB.import_db(snap.destination)
        assert restored.restored_from_snapshot
        assert len(restored.forwards) == len(wf.forwards)
    finally:
        snap.stop()
        wf.del_ref(snap)


def test_multihost_nonzero_process_skips_write(tmp_path, monkeypatch):
    import veles_tpu.snapshotter as snapshotter_mod
    wf = build(2, tmp_path)
    snap = wf.snapshotter
    snap.skip = Bool(False)
    monkeypatch.setattr(snapshotter_mod, "_is_writer_process", False)
    snap.run()
    assert snap.destination is None
    assert not glob.glob(str(tmp_path / "blob*"))
    # process 0 writes as usual
    monkeypatch.setattr(snapshotter_mod, "_is_writer_process", True)
    snap.run()
    assert snap.flush()
    assert glob.glob(str(tmp_path / "blob*.pickle.gz"))


def test_profiler_attributes_snapshot_stall_slice(tmp_path):
    wf = build(3, tmp_path)
    prof = wf.attach_profiler(fence=False)
    wf.run()
    prof.detach()
    summary = prof.summary()
    assert summary["steps"] > 0
    assert summary.get("snapshot_stall_s", 0) > 0
    assert "snapshot" in summary["phase_pct"]
    # the wrapper came off cleanly: a fresh run() is the unit's own
    assert "run" not in wf.snapshotter.__dict__


class _DeepcopyBomb:
    """Pickles fine; refuses deepcopy — models exotic unit state."""

    def __reduce__(self):
        return (_DeepcopyBomb, ())

    def __deepcopy__(self, memo):
        raise RuntimeError("no deepcopy for you")


def test_capture_fallback_on_deepcopy_failure(tmp_path):
    """An uncopyable workflow falls back to the synchronous write path
    instead of losing the shot."""
    wf = build(2, tmp_path)
    snap = wf.snapshotter
    snap.skip = Bool(False)
    snap.time_interval = 0
    wf.poison = _DeepcopyBomb()
    try:
        snap.run()
        assert os.path.exists(snap.destination)   # written inline
        assert getattr(snap, "_writer_", None) is None
        restore(snap.destination)
    finally:
        del wf.poison
