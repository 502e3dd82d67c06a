"""What the chip's compiler says, asked from a machine without the chip.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (``jax.experimental.topologies``), so every
hand kernel of the main path is compiled at real widths for a v5e on
every test run: a block shape the Pallas TPU lowering refuses, or a
kernel that outgrows VMEM, fails here and costs no chip time.  Interpret
mode cannot see either.  A compile that passes is not a chip run
(``chip_smoke.py`` is); nothing here executes.

The last test rehearses ``chip_smoke.py`` itself on the CPU at tiny
sizes: its whole control flow runs, and it must end in failure, because
only a TPU may make it say ok.
"""

import json
import os
import subprocess
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from veles_tpu import backends  # noqa: E402
from veles_tpu.znicz import (flash_attention as fa, gemm, lrn,  # noqa: E402
                             paged_attention as pa)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

@pytest.fixture(scope="module")
def topology():
    """The described v5e:2x2, asked for once a module and only when a
    test of this file runs: describing it loads the TPU's library, which
    one process at a time may hold, so nothing here does it while the
    file is imported (every xdist worker imports every test file)."""
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — no libtpu, no description
        pytest.skip("the v5e:2x2 topology cannot be described here "
                    "(%s: %s)" % (type(exc).__name__, exc))


@pytest.fixture()
def v5e(monkeypatch, topology):
    """One described v5e chip; kernels out of interpret mode; JAX's
    persistent cache off (an entry compiled for a described chip is
    written but cannot be read back without one, and warns)."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(backends, "on_tpu", lambda: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topology.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compile(fn, *structs):
    """The compiled program's text; raises what the chip's compiler
    would raise."""
    text = jax.jit(fn).lower(*structs).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


# (tag, B, H, D, block_size, max_blocks, pool dtype): the smoke server's
# geometry (chip_smoke.py DECODE_GEOMETRY over the d256/8-head flagship)
# and one a deployment would run (16 heads of 128, 128-token pages)
_PAGED = [("smoke-f32", 16, 8, 32, 16, 16, jnp.float32),
          ("smoke-int8", 16, 8, 32, 16, 16, jnp.int8),
          ("real-bf16", 16, 16, 128, 128, 16, jnp.bfloat16)]


@pytest.mark.parametrize("entry", ["decode", "prefill", "verify"])
@pytest.mark.parametrize("geometry", _PAGED, ids=[g[0] for g in _PAGED])
def test_paged_attention_compiles_for_v5e(v5e, geometry, entry):
    _, b, h, d, bs, nb, dtype = geometry
    quantized = dtype == jnp.int8

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    n_pool = b * nb + 1
    qdt = jnp.float32 if quantized else dtype
    pool = s((n_pool, bs, h, d), dtype)
    scales = [s((n_pool, h), jnp.float32)] * 2 if quantized else []

    def kw(*sc):
        return dict(zip(("k_scales", "v_scales"), sc))
    table, lengths = s((b, nb), jnp.int32), s((b,), jnp.int32)
    scalar = s((), jnp.int32)
    if entry == "decode":
        _compile(lambda q, k, v, t, n, *sc: pa.paged_attention(
            q, k, v, t, n, **kw(*sc)),
            s((b, h, d), qdt), pool, pool, table, lengths, *scales)
    elif entry == "prefill":
        _compile(lambda q, k, v, row, start, n, *sc:
                 pa.paged_prefill_attention(q, k, v, row, start, n,
                                            **kw(*sc)),
                 s((32, h, d), qdt), pool, pool, s((nb,), jnp.int32),
                 scalar, scalar, *scales)
    else:
        _compile(lambda q, k, v, t, n, *sc: pa.paged_verify_attention(
            q, k, v, t, n, **kw(*sc)),
            s((b, 4, h, d), qdt), pool, pool, table, lengths, *scales)


# (B, T, H, D, dtype, window)
_FLASH = [(2, 2048, 8, 64, jnp.float32, None),
          (2, 2048, 8, 64, jnp.bfloat16, None),
          (1, 4096, 16, 128, jnp.bfloat16, None),
          (1, 16384, 8, 64, jnp.float32, 512)]


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "grad"])
@pytest.mark.parametrize(
    "shape", _FLASH,
    ids=["B%d-T%d-H%d-D%d-%s-w%s" % (b, t, h, d, jnp.dtype(dt).name, w)
         for b, t, h, d, dt, w in _FLASH])
def test_flash_attention_compiles_for_v5e(v5e, shape, grad):
    b, t, h, d, dtype, window = shape
    q = jax.ShapeDtypeStruct((b, t, h, d), dtype, sharding=v5e)

    def attend(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=window)
    if not grad:
        _compile(attend, q, q, q)
        return
    text = _compile(jax.grad(
        lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)), q, q, q)
    # forward, dq and dk/dv kernels
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("level", [0, 1, 2])
def test_precise_matmul_compiles_for_v5e(v5e, level):
    a = jax.ShapeDtypeStruct((1024, 1024), jnp.float32, sharding=v5e)
    _compile(lambda a, b: gemm.precise_matmul(a, b, level, False), a, a)


def test_quantized_matmul_compiles_for_v5e(v5e):
    a = jax.ShapeDtypeStruct((1024, 1024), jnp.float32, sharding=v5e)
    w = jax.ShapeDtypeStruct((1024, 1024), jnp.int8, sharding=v5e)
    s = jax.ShapeDtypeStruct((1024,), jnp.float32, sharding=v5e)
    _compile(lambda a, w, s: gemm.quantized_matmul(a, w, s,
                                                   interpret=False),
             a, w, s)


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "grad"])
def test_pallas_lrn_compiles_for_v5e(v5e, grad):
    x = jax.ShapeDtypeStruct((128, 55, 55, 96), jnp.float32, sharding=v5e)

    def run(x):
        return lrn.pallas_lrn(x, 5, 1e-4, 0.75, 2.0)
    text = _compile(jax.grad(lambda x: run(x).sum()) if grad else run, x)
    # the kernels carry their names into the program (and so into a
    # device trace); the gradient of a sum needs no forward kernel
    assert ("lrn_backward" if grad else "lrn_forward") in text


_REHEARSAL = """
import sys
sys.path.insert(0, %(repo)r)
import chip_smoke as cs
cs.ALEXNET.update(batch=4, side=67, n_classes=10, n_train=8, n_valid=4,
                  epochs=1)
cs.TRAINER_RUNS = (("fused", "float32"), ("scan", "bfloat16"))
cs.FLAGSHIP.update(stages=1, experts=2, d=16, heads=2, hidden=32, vocab=32)
cs.DECODE_GEOMETRY.update(max_batch=2, block_size=4, max_prompt_len=4,
                          max_new_tokens=4)
cs.DECODE_REQUESTS = ((3, 4), (4, 2))
cs.RING.update(t=32, heads=1, d=8)
cs.FLASH_WINDOW = 8
cs.MNIST.update(max_batch=2, requests=(1, 2))
sys.exit(cs.run(backend="cpu"))
"""


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "grad"])
@pytest.mark.parametrize("kv_heads", [8, 32], ids=["grouped", "one-count"])
def test_grouped_query_flash_compiles_for_v5e(v5e, kv_heads, grad):
    """The plain flash kernels as the grouped-query block calls them: 32
    query heads of 64 on 8 key-value heads (and on 32, the group of 1),
    two sequences of 8,192, bfloat16, at the block sizes the kernel
    layer picks: head size 64 tiles, K and V stay at their head count."""

    def struct(heads):
        return jax.ShapeDtypeStruct((2, 8192, heads, 64), jnp.bfloat16,
                                    sharding=v5e)

    def attend(q, k, v):
        return fa.flash_attention(q, k, v, causal=True)
    operands = (struct(32), struct(kv_heads), struct(kv_heads))
    if not grad:
        assert "gqa_flash_fwd" in _compile(attend, *operands)
        return
    text = _compile(jax.grad(
        lambda *a: attend(*a).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)), *operands)
    for kernel in ("gqa_flash_fwd", "gqa_flash_dq", "gqa_flash_dkv"):
        assert kernel in text, kernel
    # dK and dV leave the kernel per KEY-VALUE head
    assert "bf16[%d,8192,64]" % (2 * kv_heads) in text


@pytest.mark.parametrize("window", [1024, None], ids=["window", "full"])
def test_wide_grouped_heads_compile_for_v5e(v5e, window):
    """The same kernels as a depth of window layers beside full ones
    calls them: 32 query heads of 128 on 4 key-value heads (groups of
    8), two sequences of 8,192, bfloat16, forward and backward, inside a
    window of 1,024 on the banded grids and without one, at the block
    sizes the kernel layer picks; a window's calls under their own
    names."""

    def struct(heads):
        return jax.ShapeDtypeStruct((2, 8192, heads, 128), jnp.bfloat16,
                                    sharding=v5e)

    def attend(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=window)
    text = _compile(jax.grad(
        lambda *a: attend(*a).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)), struct(32), struct(4), struct(4))
    family = "gqa_window_flash_" if window else "gqa_flash_"
    other = "gqa_flash_" if window else "gqa_window_flash_"
    for kernel in ("fwd", "dq", "dkv"):
        assert family + kernel in text, kernel
    assert other not in text
    # dK and dV leave the kernel per KEY-VALUE head
    assert "bf16[8,8192,128]" in text


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "grad"])
@pytest.mark.parametrize("shared", [True, False], ids=["shared", "copied"])
def test_latent_flash_attention_compiles_for_v5e(v5e, shared, grad):
    """The latent-attention kernels at the widths of a 32-head model
    (192-wide query-key heads in a 128 and a 64 part, 128-wide values),
    two sequences of 8,192, bfloat16, with the rope key one a token."""
    b, h, t = 2, 32, 8192

    def struct(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=v5e)
    operands = (struct(b, h, t, 128), struct(b, h, t, 64),
                struct(b, h, t, 128),
                struct(b, t, 64) if shared else struct(b, h, t, 64),
                struct(b, h, t, 128))
    if not grad:
        text = _compile(fa.mla_flash_attention, *operands)
        assert "mla_flash_fwd" in text
        return
    text = _compile(jax.grad(
        lambda *a: fa.mla_flash_attention(*a).astype(jnp.float32).sum(),
        argnums=(0, 1, 2, 3, 4)), *operands)
    for kernel in ("mla_flash_fwd", "mla_flash_dq", "mla_flash_dkv"):
        assert kernel in text, kernel


#: (rows in the row buffer, held experts, K, N) of the decoder cells'
#: grouped products: Kanana's (``gate_up``, ``down``), LFM2's, Mellum's
_GROUPED = {"gate_up": (24576, 16, 2048, 1536), "down": (24576, 16, 768, 2048),
            "lfm2_gate_up": (16384, 8, 2048, 3072),
            "lfm2_down": (16384, 8, 1536, 2048),
            "mellum_gate_up": (65536, 16, 2304, 1792),
            "mellum_down": (65536, 16, 896, 2304)}


def _pallas_blocks(jaxpr):
    """(block shape, array shape) of every operand of every Pallas call
    in ``jaxpr`` and the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            for mapping in eqn.params["grid_mapping"].block_mappings:
                yield mapping.block_shape, mapping.array_aval.shape
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            if hasattr(inner, "eqns"):
                yield from _pallas_blocks(inner)


@pytest.mark.parametrize("shape", list(_GROUPED.values()),
                         ids=list(_GROUPED))
def test_grouped_matmul_compiles_for_v5e(v5e, shape):
    """The expert layer's grouped products of the three decoder cells at
    their row buffers, forward and gradients: each kernel's tiles fit
    the chip's VMEM, and every tile divides its dimension, so no kernel
    runs a padded, masked remainder tile."""
    m, g, k, n = shape
    lhs = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=v5e)
    rhs = jax.ShapeDtypeStruct((g, k, n), jnp.bfloat16, sharding=v5e)
    sizes = jax.ShapeDtypeStruct((g,), jnp.int32, sharding=v5e)
    step = jax.value_and_grad(
        lambda a, b, s: gemm.grouped_matmul(a, b, s).astype(
            jnp.float32).sum(), argnums=(0, 1))
    text = _compile(step, lhs, rhs, sizes)
    assert "gmm" in text and "tgmm" in text
    assert text.count("tpu_custom_call") >= 3     # forward, two gradients
    blocks = list(_pallas_blocks(jax.make_jaxpr(step)(lhs, rhs,
                                                       sizes).jaxpr))
    assert len(blocks) >= 9                       # three operands a kernel
    for block, dims in blocks:
        for tile, dim in zip(block, dims):
            assert dim % getattr(tile, "block_size", dim) == 0, (block, dims)


# -- where the resident data set lies for the per-step programs ---------------

#: ``alexnet_step``'s resident set: 8,192 + 512 float32 images
SET = (8704, 227, 227, 3)


@pytest.fixture(scope="module")
def gather_step():
    """A per-step trainer (``FusedTrainStep``, the gather inside the
    step) at batch 256 whose first layer is AlexNet's conv1, initialized
    on the CPU over 512 blank images: its own functions are then lowered
    for the real set on the described chip.  The compiled programs are
    kept beside it, one compile each."""
    import numpy
    from veles_tpu.backends import Device
    from veles_tpu.config import root
    from veles_tpu.znicz.samples import alexnet

    class Blank(alexnet.SyntheticImagenetLoader):
        def load_data(self):
            self.original_data.mem = numpy.zeros((512,) + SET[1:],
                                                 numpy.float32)
            self.original_labels = [i % 10 for i in range(512)]
            self.class_lengths[:] = [0, 256, 256]
    conv1, _, pool1 = root.alexnet.layers[:3]
    wf = alexnet.create_workflow(
        loader_factory=Blank, loader={"minibatch_size": 256},
        layers=[conv1, pool1,
                {"type": "softmax", "->": {"output_sample_shape": 10},
                 "<-": {"learning_rate": 0.01}}],
        decision={"max_epochs": 1, "silent": True})
    wf.initialize(device=Device(backend="cpu"))
    assert wf.fused_step._use_gather_
    return wf.fused_step, {}


def _gather_program(gather_step, v5e, which):
    """The gather step's ``which`` program compiled for the set ``SET``
    on the described chip: ``train`` through ``_lower_gather_train``, as
    ``_place_data`` compiles it (the set's layout left to the compiler);
    ``eval`` against the layout ``train`` asked for, as its ``AotStep``
    lowers it at the first call; ``train-default`` / ``eval-default``
    against the layout a ``device_put`` gives."""
    from jax.experimental.layout import Format
    step, compiled = gather_step
    if which in compiled:
        return compiled[which]

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e)

    def struct(shape, dtype, sharding=v5e):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    data, y = struct(SET, jnp.float32), struct(SET[:1], jnp.int32)
    params, opt, macc = (jax.tree.map(on_chip, t) for t in (
        step._params_, step._opt_, step._macc_))
    idx, size = struct((256,), jnp.int32), struct((), jnp.int32)
    train = (y, params, opt, macc, idx, size, size,
             struct((), jnp.float32))
    if which == "train":
        lowered = step._lower_gather_train(data, *train)
    elif which == "train-default":
        lowered = step._train_step_g_.lower(data, *train)
    else:
        if which == "eval":
            asked = _gather_program(gather_step, v5e, "train")
            data = struct(SET, jnp.float32, Format(
                asked.input_formats[0][0].layout, v5e))
        lowered = step._eval_step_g_.lower(data, y, params, macc, idx, size)
    compiled[which] = lowered.compile()
    return compiled[which]


def _whole_set_instructions(compiled):
    """The optimized program's instructions whose RESULT has the whole
    set's shape, the parameter apart."""
    import re
    return re.findall(
        r"^\s*(?:ROOT )?%%\S+ = \w+\[%s\]\S* (?!parameter\()\S+"
        % ",".join(map(str, SET)), compiled.as_text(), re.M)


@pytest.mark.parametrize("case", ["train", "eval", "train-default",
                                  "eval-default", "batch-major"])
def test_gather_step_reads_rows_of_a_set_placed_as_its_compiler_asks(
        gather_step, v5e, case):
    """From the layout the v5e's compiler chooses for the set, batch
    dimension major-most, the train and the evaluation program gather
    their 256 rows and no instruction makes anything of the set's size.
    The control: from the layout ``device_put`` gives (batch dimension
    minor-most: 8,704 = 68 x 128 pads no lane) each program first copies
    the whole set to bfloat16, 13.5 ms a step on the chip (PERF.md
    section 6, PR 29).  When a compiler no longer does that the control
    fails, and ``FusedTrainStep._place_data`` can go with this test."""
    if case == "batch-major":
        chosen = _gather_program(gather_step, v5e, "train").input_formats
        default = _gather_program(gather_step, v5e,
                                  "train-default").input_formats
        assert chosen[0][0].layout.major_to_minor[0] == 0
        assert default[0][0].layout.major_to_minor[-1] == 0
        # and no other argument's layout was left open
        assert jax.tree.leaves(chosen[0][1:]) \
            == jax.tree.leaves(default[0][1:])
        return
    found = _whole_set_instructions(_gather_program(gather_step, v5e, case))
    if case.endswith("default"):
        assert len(found) == 1, found
        assert " copy(" in found[0] and "bf16[" in found[0], found
    else:
        assert found == []


def test_chip_smoke_rehearsal_on_cpu_runs_every_phase_and_fails():
    """The control flow of ``chip_smoke.py`` end to end at tiny sizes on
    the CPU: every phase does its work and passes its functional checks
    (training lowers the loss, the servers answer over HTTP and agree
    with their references), and the run still ends non-zero with
    ``"ok": false``, on the device checks alone."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", _REHEARSAL % {"repo": REPO}], env=env,
        cwd=REPO, capture_output=True, text=True, timeout=600)
    lines = proc.stdout.strip().splitlines()
    assert proc.returncode == 1, (proc.returncode, proc.stderr[-2000:])
    verdict = json.loads(lines[-1])
    assert verdict == {"ok": False, "device": {
        "platform": "cpu", "kind": verdict["device"]["kind"],
        "count": verdict["device"]["count"]}}
    phases = {ln.split()[0][len("phase="):]: ln for ln in lines
              if ln.startswith("phase=")}
    assert list(phases) == ["trainer", "kernels", "decode_server",
                            "export_serve"]
    for name, line in phases.items():
        assert " FAILED " in line, line
        failed = line.split(" failed=[", 1)[1]
        # nothing functional failed: only "is it the TPU" checks did
        assert "raised" not in failed, line
        assert all("TPU device" in what or "tpu_custom_call" in what
                   for what in failed.rstrip("]").split("; ")), line
    assert "loss on a fixed minibatch fell" in phases["trainer"]
    assert "served tokens are the teacher-forced reference's argmax" \
        in phases["decode_server"]
    assert "served predictions" in phases["export_serve"]
    # and with nothing steered, the script stops before its first phase
    gate = subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py")],
                          env=env, cwd=REPO, capture_output=True,
                          text=True, timeout=120)
    assert gate.returncode == 2
    assert json.loads(gate.stdout.strip().splitlines()[-1])["ok"] is False
    assert "phase=" not in gate.stdout
