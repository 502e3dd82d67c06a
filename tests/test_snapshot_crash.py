"""Crash-safety harness (ISSUE 4 satellite): SIGKILL a child process
mid-snapshot-write and assert the on-disk invariants the atomic
tmp-write + fsync + rename protocol guarantees — every file at its
final name is a complete, loadable snapshot; at most one ``*.tmp``
orphan; ``_current`` (when present) always resolves to a loadable file.
"""

import glob
import os
import signal
import subprocess
import sys
import time

import pytest

from veles_tpu.snapshotter import SnapshotterToFile
from veles_tpu.checkpoint import (import_dir, list_checkpoints,
                                  quarantine_partials, resolve_checkpoint)

_CHILD = r"""
import os, sys
sys.path.insert(0, %(repo)r)
import numpy
from veles_tpu.config import root
root.common.snapshot.compression_level = 1   # big, fast writes
from veles_tpu.snapshotter import SnapshotterToFile
from veles_tpu.units import TrivialUnit
from veles_tpu.workflow import Workflow

wf = Workflow(None, name="crashwf")
unit = TrivialUnit(wf)
# incompressible payload: the gz stream stays ~8 MiB so each write is
# long enough for the parent's SIGKILL to land mid-write
unit.blob = numpy.random.RandomState(0).standard_normal(
    (1 << 20,)).astype(numpy.float32)
snap = SnapshotterToFile(wf, prefix="crash", directory=%(dir)r,
                         time_interval=0, compression="gz",
                         async_write=%(async_write)r)
while True:
    snap._counter += 1
    snap.export()
    snap.flush()
    print("WROTE", flush=True)
"""


def _run_crash_drill(tmp_path, async_write):
    snapdir = str(tmp_path / ("async" if async_write else "sync"))
    os.makedirs(snapdir)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-c",
         _CHILD % {"repo": repo, "dir": snapdir,
                   "async_write": async_write}],
        stdout=subprocess.PIPE, env=env)
    try:
        # wait for the first complete snapshot, then kill mid-loop
        line = proc.stdout.readline()
        assert b"WROTE" in line, "child never wrote a snapshot"
        time.sleep(0.12)          # land somewhere inside a later write
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        proc.stdout.close()
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    return snapdir


@pytest.mark.parametrize("async_write", [False, True],
                         ids=["sync-fallback", "async-writer"])
def test_sigkill_mid_write_leaves_only_complete_snapshots(
        tmp_path, async_write):
    snapdir = _run_crash_drill(tmp_path, async_write)

    finals = glob.glob(os.path.join(snapdir, "crash*.pickle.gz"))
    orphans = glob.glob(os.path.join(snapdir, "*.tmp"))
    assert finals, "no complete snapshot survived"
    # at most one in-flight tmp (the write the kill interrupted)
    assert len(orphans) <= 1, orphans
    # every file at its final name is complete and loadable
    for path in finals:
        wf = SnapshotterToFile.import_file(path)
        assert wf.restored_from_snapshot
    # _current, when it exists, resolves to a loadable file
    current = os.path.join(snapdir, "crash_current")
    if os.path.islink(current):
        target = os.path.join(snapdir, os.readlink(current))
        assert os.path.exists(target), "dangling crash_current"
        SnapshotterToFile.import_file(current)


_SHARD_CHILD = r"""
import os, sys
sys.path.insert(0, %(repo)r)
import numpy
from veles_tpu.checkpoint import SnapshotterToShards
from veles_tpu.units import TrivialUnit
from veles_tpu.workflow import Workflow

wf = Workflow(None, name="crashwf")
unit = TrivialUnit(wf)
rng = numpy.random.RandomState(0)
snap = SnapshotterToShards(wf, prefix="crash", directory=%(dir)r,
                           time_interval=0, min_tensor_bytes=1,
                           chunk_bytes=1 << 16)
while True:
    # MUTATE the payload: every export must write fresh chunks (a
    # dedupe hit would make the window the kill can land in tiny)
    unit.blob = rng.standard_normal((1 << 20,)).astype(numpy.float32)
    snap._counter += 1
    snap.export()
    snap.flush()
    print("WROTE", flush=True)
"""


def test_sigkill_mid_shard_checkpoint_leaves_previous_restorable(tmp_path):
    """SIGKILL during a sharded checkpoint: nothing torn ever appears
    at a final ``*.ckpt`` name, ``_current`` keeps resolving to a
    loadable checkpoint, and a later startup quarantines the
    ``.tmp``/``.parts`` partials the kill stranded."""
    snapdir = str(tmp_path / "shards")
    os.makedirs(snapdir)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-c",
         _SHARD_CHILD % {"repo": repo, "dir": snapdir}],
        stdout=subprocess.PIPE, env=env)
    try:
        line = proc.stdout.readline()
        assert b"WROTE" in line, "child never wrote a checkpoint"
        time.sleep(0.12)          # land inside a later chunked write
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        proc.stdout.close()
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)

    # every checkpoint at a final name is complete and restores
    finals = list_checkpoints(snapdir)
    assert finals, "no complete checkpoint survived"
    for ckpt in finals:
        wf = import_dir(ckpt)
        assert wf.restored_from_snapshot
    # _current always resolves to one of the complete checkpoints
    current = os.path.join(snapdir, "crash_current")
    if os.path.islink(current):
        resolved = resolve_checkpoint(current)
        assert resolved in [os.path.realpath(p) for p in finals]
    # the interrupted write left at most one staging dir of each kind —
    # never a torn dir at a final name
    partials = [n for n in os.listdir(snapdir)
                if n.endswith(".ckpt.tmp") or n.endswith(".ckpt.parts")]
    assert len(partials) <= 2, partials
    # startup recovery sweeps them aside
    moved = quarantine_partials(snapdir)
    assert len(moved) == len(partials)
    for path in moved:
        assert ".quarantine" in os.path.basename(path)
    assert not [n for n in os.listdir(snapdir)
                if n.endswith(".ckpt.tmp") or n.endswith(".ckpt.parts")]
