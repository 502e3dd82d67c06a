"""The plain flash kernels' grids, step by step: which steps compute
(clear blocks unmasked, edge blocks masked), which blocks the streamed
operands fetch, and how wide a window's band is, against a brute force
over the elementwise mask; and the kernels' results over the same
geometries against ``attention_reference``.  Pallas interpret mode on
the CPU; the cells' widths are compiled for the described chip in
``tests/test_chip_compile.py``."""

import itertools

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.parallel.ring import attention_reference
from veles_tpu.znicz import flash_attention as fa

KERNELS = ("fwd", "dq", "dkv")

# T, (block_q, block_k), window, group: blocks equal and unequal, windows
# narrower than a block, no multiple of one, and several blocks wide
SWEEP = list(itertools.product((256, 384), ((32, 32), (64, 32), (32, 64)),
                               (None, 32, 40, 96), (1, 4, 8)))


def sweep_id(case):
    t, (bq, bk), window, group = case
    return "T%d-q%d-k%d-w%s-g%d" % (t, bq, bk, window, group)


def pairs(t, bq, bk, window):
    """(needed, clear) of every (query block, key block) pair, from the
    elementwise mask: any pair of the block visible, every pair."""
    rows, cols = numpy.arange(t)[:, None], numpy.arange(t)[None, :]
    vis = cols <= rows
    if window is not None:
        vis &= cols > rows - window
    blocks = vis.reshape(t // bq, bq, t // bk, bk)
    return blocks.any(axis=(1, 3)), blocks.all(axis=(1, 3))


@pytest.mark.parametrize("window", [None, 1024])
@pytest.mark.parametrize("group", [1, 4, 8])
def test_the_cells_geometries(window, group):
    """Two sequences of 8,192 at blocks of 1,024: a full layer computes 36
    of its 64 steps a query head, 28 of them unmasked; a window layer of
    1,024 runs a band of 2 blocks each way, 15 computed steps a head,
    every one an edge; no fetch is wasted and no block is fetched more
    often than a step computes on it."""
    census = fa.block_census(8192, 1024, 1024, window, True, group)
    for kernel in KERNELS:
        c = census[kernel]
        assert c["wasted"] == 0, kernel
        assert c["fetches"] <= c["clear"] + c["edge"], kernel
        if window is None:
            assert c["steps"] == 64 * group, kernel
            assert (c["clear"], c["edge"]) == (28 * group, 8 * group)
        else:
            assert c["steps"] == 16 * group, kernel
            assert (c["clear"], c["edge"]) == (0, 15 * group)
    if window is None:
        assert census["fwd"]["fetches"] <= 36 * group
    else:
        # each key block once a query head, and nothing more
        assert census["fwd"]["fetches"] == 8 * group
        # no clear block: the window layers' kernels hold the masked body
        # alone
        assert fa._Grid(8192, 1024, 1024, 1024, True).classes == (False,
                                                                   True)
        assert fa._kband_size(8192, 1024, 1024, 1024) == 2
        assert fa._qband_size(8192, 1024, 1024, 1024) == 2


@pytest.mark.parametrize("case", SWEEP, ids=[sweep_id(c) for c in SWEEP])
def test_every_visible_pair_is_computed_and_no_fetch_wasted(case):
    t, (bq, bk), window, group = case
    needed, clear = pairs(t, bq, bk, window)
    census = fa.block_census(t, bq, bk, window, True, group)
    for kernel in KERNELS:
        c = census[kernel]
        assert c["wasted"] == 0, kernel
        assert c["clear"] == group * clear.sum(), kernel
        assert c["edge"] == group * (needed & ~clear).sum(), kernel
        assert c["fetches"] <= c["clear"] + c["edge"], kernel
    # a kernel holds a body only for the classes its grid has
    assert fa._Grid(t, bq, bk, window, True).classes == (
        bool(clear.any()), bool((needed & ~clear).any()))


@pytest.mark.parametrize("case", SWEEP[::3], ids=[sweep_id(c)
                                                  for c in SWEEP[::3]])
def test_a_call_without_causality_computes_every_block_unmasked(case):
    t, (bq, bk), _, group = case
    census = fa.block_census(t, bq, bk, None, False, group)
    for kernel in KERNELS:
        c = census[kernel]
        assert c["steps"] == c["clear"] == group * (t // bq) * (t // bk)
        assert (c["edge"], c["wasted"]) == (0, 0)
    assert fa._Grid(t, bq, bk, None, False).classes == (True, False)


BANDS = sorted({(t, bq, bk, w) for t, (bq, bk), w, _ in SWEEP
                if w is not None} | {
    (8192, bq, bk, w) for bq in (256, 512, 1024) for bk in (256, 512, 1024)
    for w in (1000, 1024, 1500, 4096)})


@pytest.mark.parametrize("t,bq,bk,window", BANDS)
def test_a_band_is_as_wide_as_the_widest_run_and_no_wider(t, bq, bk, window):
    needed, _ = pairs(t, bq, bk, window) if t <= 384 else (None, None)
    if needed is None:      # an 8,192-square mask: count by the rows
        k_runs = [sum(1 for jk in range(t // bk)
                      if jk * bk <= iq * bq + bq - 1
                      and jk * bk + bk - 1 > iq * bq - window)
                  for iq in range(t // bq)]
        q_runs = [sum(1 for iq in range(t // bq)
                      if jk * bk <= iq * bq + bq - 1
                      and jk * bk + bk - 1 > iq * bq - window)
                  for jk in range(t // bk)]
    else:
        k_runs, q_runs = needed.sum(axis=1), needed.sum(axis=0)
    assert fa._kband_size(t, bq, bk, window) == max(k_runs)
    assert fa._qband_size(t, bq, bk, window) == max(q_runs)
    # never wider than the worst case over phases that it replaced
    assert fa._kband_size(t, bq, bk, window) <= (bq + window - 2) // bk + 2
    assert fa._qband_size(t, bq, bk, window) <= (bk + window - 2) // bq + 2


def weighed(attend):
    return lambda q, k, v: jnp.sum(jnp.sin(attend(q, k, v)) ** 2)


@pytest.mark.parametrize("case", SWEEP, ids=[sweep_id(c) for c in SWEEP])
def test_outputs_and_gradients_over_the_sweep(case):
    t, (bq, bk), window, group = case
    rng = numpy.random.RandomState(t + bq + 3 * bk + (window or 0) + group)

    def draw(h):
        return jnp.asarray(0.5 * rng.standard_normal((1, t, h, 8)),
                           jnp.float32)
    q, k, v = draw(group), draw(1), draw(1)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, True, None, bq, bk, window)

    def reference(q, k, v):
        return attention_reference(q, k, v, causal=True, window=window)
    numpy.testing.assert_allclose(flash(q, k, v), reference(q, k, v),
                                  rtol=2e-5, atol=2e-5)
    got = jax.grad(weighed(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(weighed(reference), argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        numpy.testing.assert_allclose(g, w, rtol=5e-4, atol=5e-4,
                                      err_msg="d" + name)
