"""Flagship composition demo: a modern MoE transformer trained over a
dp x pp x ep mesh.

The "modern demo" SURVEY §5 contemplates (VERDICT round-3 item 10): the
round-3/4 parallel primitives composed in ONE model —

- each block = causal multi-head attention (the functional core of
  ``znicz.attention`` / ``parallel.ring.attention_reference``) + an
  RMS-norm + a **switch-MoE feed-forward** whose experts shard over the
  ``expert`` mesh axis (``parallel.moe._moe_local``);
- a stack of S identical blocks pipelined over the ``pipe`` axis with
  the GPipe microbatch schedule (``parallel.pipeline._gpipe_local``);
- the batch sharded over ``data``;
- optionally the SEQUENCE sharded over ``seq``: pass ``seq_axis`` and
  the attention inside every pipelined block becomes ring attention
  (``parallel.ring._ring_attention_local``) — K/V chunks ride
  ppermutes over the seq ring while activations ride the pipe ring.

Up to FOUR mesh axes live in ONE ``shard_map`` program: the pipeline
ring ppermutes over ``pipe``, the attention ring over ``seq``, the MoE
combine psums over ``expert``, and XLA inserts the gradient all-reduce
over ``data`` — the full quintet minus tp, which composes the same way
(tensor sharding annotates the projections).

``flagship_reference`` is the single-device oracle (sequential blocks,
oracle MoE); the test asserts forward parity AND that one fused train
step on the dp2 x pp2 x ep2 8-device mesh learns
(tests/test_flagship.py).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy

from ...parallel.mesh import make_mesh
from ...parallel.moe import _moe_local, moe_capacity, moe_reference
from ...parallel.pipeline import _gpipe_local
from ...parallel.ring import _ring_attention_local, attention_reference


def init_params(stages, experts, d=16, heads=2, hidden=32, seed=0):
    """One stacked param tree: leading dim S (pipe), expert leaves
    [S, E, ...]."""
    rng = numpy.random.RandomState(seed)

    def w(*shape, scale=0.25):
        return jnp.asarray(rng.standard_normal(shape) * scale,
                           jnp.float32)

    return {
        "qkv": w(stages, d, 3 * d),
        "proj": w(stages, d, d),
        "wr": w(stages, d, experts),
        "w1": w(stages, experts, d, hidden),
        "w2": w(stages, experts, hidden, d),
    }


def _rmsnorm(h):
    return h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) +
                             1e-6)


def _expert_ffn(p, h):
    return jnp.maximum(h @ p["w1"], 0.0) @ p["w2"]


def _expert_ffn_quant(p, h):
    """The expert FFN over quantized weight leaves: both GEMMs stream
    int8/fp8 weight bytes and fold the per-output-channel scales after
    the K loop (znicz.gemm.quantized_matmul)."""
    from ..gemm import quantized_matmul
    a = jnp.maximum(quantized_matmul(h, p["w1_q"], p["w1_s"]), 0.0)
    return quantized_matmul(a, p["w2_q"], p["w2_s"])


def _quantize_weight_stack(w, dtype):
    """Per-output-channel quantization of a stacked ``[..., K, N]``
    weight (stages x experts leading dims) — the stacked counterpart of
    :func:`~veles_tpu.znicz.gemm.quantize_weight`, sliced per stage and
    per expert by the decode path's existing tree_map indexing."""
    from ..gemm import _FP8_E4M3_MAX, fp8_dtype
    w = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=-2)
    if dtype == "int8":
        scales = jnp.where(amax > 0, amax / 127.0, 1.0)
        q = jnp.clip(jnp.round(w / scales[..., None, :]), -127, 127)
        return q.astype(jnp.int8), scales.astype(jnp.float32)
    if dtype == "fp8":
        scales = jnp.where(amax > 0, amax / _FP8_E4M3_MAX, 1.0)
        return (w / scales[..., None, :]).astype(fp8_dtype()), \
            scales.astype(jnp.float32)
    raise ValueError("unknown weight dtype %r" % (dtype,))


def _attend_block(params, h, heads, seq_axis=None, vary_axes=None,
                  use_pallas=False):
    b, t, d = h.shape
    qkv = _rmsnorm(h) @ params["qkv"]
    q, k, v = (qkv[..., i * d:(i + 1) * d].reshape(b, t, heads,
                                                   d // heads)
               for i in range(3))
    if seq_axis is None:
        a = attention_reference(q, k, v, causal=True)
    else:
        # inside the full-mesh shard_map: t is this shard's chunk and
        # the K/V blocks ride the seq ring (flash recurrence);
        # use_pallas swaps in ring FLASH attention (per-hop Pallas
        # kernels, parallel/ring.py) when the chunk tiles
        local = _ring_attention_local
        if use_pallas:
            from ...parallel.ring import _ring_flash_local
            from ..flash_attention import flash_attention_supported
            if flash_attention_supported(t):
                local = _ring_flash_local
        a = local(
            q, k, v, axis_name=seq_axis, causal=True,
            scale=1.0 / math.sqrt(d // heads), vary_axes=vary_axes)
    return h + a.reshape(b, t, d) @ params["proj"]


def _block_sharded(params, h, *, heads, capacity, k, seq_axis=None,
                   vary_axes=None, use_pallas=False):
    """One transformer block INSIDE the full-mesh shard_map: expert
    leaves carry a leading local-expert dim (1), the MoE dispatch
    psums over the bound ``expert`` axis, and (when ``seq_axis`` is
    bound) attention rides the seq ring."""
    h = _attend_block(params, h, heads, seq_axis=seq_axis,
                      vary_axes=vary_axes, use_pallas=use_pallas)
    b, t, d = h.shape
    flat = _rmsnorm(h).reshape(b * t, d)
    moe = _moe_local({"w1": params["w1"], "w2": params["w2"]},
                     params["wr"], flat, expert_apply=_expert_ffn,
                     capacity=capacity, axis_name="expert", k=k)
    return h + moe.reshape(b, t, d)


def _block_oracle(params, h, *, heads, capacity, k, seq_shards=1):
    """Same block on one device: oracle MoE over the full [E,...]
    stack.  Attention is GLOBAL over T (ring attention equals full
    attention); the MoE queues replay per seq shard, matching the
    sharded path's per-chunk routing."""
    h = _attend_block(params, h, heads)
    b, t, d = h.shape
    normed = _rmsnorm(h)
    outs = []
    for c in range(seq_shards):
        chunk = normed[:, c * (t // seq_shards):
                       (c + 1) * (t // seq_shards)]
        flat = chunk.reshape(-1, d)
        moe = moe_reference(_expert_ffn,
                            {"w1": params["w1"], "w2": params["w2"]},
                            params["wr"], flat, capacity, k=k)
        outs.append(moe.reshape(b, t // seq_shards, d))
    return h + jnp.concatenate(outs, axis=1)


def flagship_apply(params, x, mesh, heads=2, microbatches=None,
                   capacity_factor=2.0, k=1, seq_axis=None,
                   use_pallas=False):
    """The pipelined sharded forward: x [B, T, D] with B over ``data``,
    blocks over ``pipe``, experts over ``expert`` — and T over
    ``seq_axis`` when given (ring attention inside each stage)."""
    from jax.sharding import PartitionSpec as P
    s = mesh.shape["pipe"]
    e = mesh.shape["expert"]
    # the pipeline shard takes p[0] of ITS slice and the MoE shard
    # routes to ITS local experts: stacked params larger than the mesh
    # axes would silently truncate to stage 0 / expert 0 (a 1-device
    # mesh once inflated a bench 4x this way) — fail loudly instead
    got_s = jax.tree_util.tree_leaves(params)[0].shape[0]
    got_e = params["w1"].shape[1]
    if got_s != s or got_e != e:
        raise ValueError(
            "flagship params are stacked for %d stages x %d experts "
            "but the mesh has pipe=%d x expert=%d — sizes must match "
            "(a mismatch would silently run a truncated model)"
            % (got_s, got_e, s, e))
    dp = mesh.shape.get("data", 1)
    sp = mesh.shape.get(seq_axis, 1) if seq_axis else 1
    m = microbatches if microbatches is not None else 2 * s
    b, t, d = x.shape
    tokens_per_mb = (b // dp // m) * (t // sp)
    capacity = moe_capacity(tokens_per_mb, e, capacity_factor, k)
    vary = tuple(a for a in ("data", seq_axis)
                 if a and a in mesh.shape) + ("pipe",)
    block = functools.partial(_block_sharded, heads=heads,
                              capacity=capacity, k=k,
                              seq_axis=seq_axis, vary_axes=vary,
                              use_pallas=use_pallas)
    specs = {"qkv": P("pipe"), "proj": P("pipe"), "wr": P("pipe"),
             "w1": P("pipe", "expert"), "w2": P("pipe", "expert")}
    x_spec = P("data", seq_axis) if seq_axis else P("data")
    fn = jax.shard_map(
        functools.partial(_gpipe_local, block_apply=block, n_stages=s,
                          microbatches=m, axis_name="pipe"),
        mesh=mesh,
        in_specs=({n: specs[n] for n in params}, x_spec),
        out_specs=x_spec)
    return fn(params, x)


def flagship_reference(params, x, heads=2, microbatches=None,
                       capacity_factor=2.0, k=1, data_shards=1,
                       pipe_stages=None, seq_shards=1):
    """Single-device oracle with the SAME capacity semantics: the
    sharded path routes each (data shard, microbatch, seq chunk)
    independently, so the oracle replays that slicing."""
    s = jax.tree_util.tree_leaves(params)[0].shape[0] \
        if pipe_stages is None else pipe_stages
    m = microbatches if microbatches is not None else 2 * s
    b, t, d = x.shape
    tokens_per_mb = (b // data_shards // m) * (t // seq_shards)
    e = params["wr"].shape[-1]
    capacity = moe_capacity(tokens_per_mb, e, capacity_factor, k)
    chunks = x.reshape(data_shards * m, b // data_shards // m, t, d)
    outs = []
    for chunk in chunks:
        h = chunk
        for i in range(s):
            params_i = jax.tree.map(lambda p: p[i], params)
            h = _block_oracle(params_i, h, heads=heads,
                              capacity=capacity, k=k,
                              seq_shards=seq_shards)
        outs.append(h)
    return jnp.concatenate(outs).reshape(b, t, d)


def demo_mesh():
    """The 8-device dp2 x pp2 x ep2 composition mesh (CPU-virtual in
    tests, a pod slice in production)."""
    return make_mesh({"data": 2, "pipe": 2, "expert": 2})


# -- causal decode over a paged KV cache --------------------------------------
#
# The serving-side face of the flagship model (ISSUE 6): a tied
# token embedding turns the [B, T, D] -> [B, T, D] trainer into a
# generate-style language model, and the per-layer K/V of every served
# sequence lives in the serving pool's fixed-size blocks
# (znicz.paged_attention) instead of a rectangular [B, T_max] cache.
# ``prefill`` runs the prompt through the dense causal forward ONCE
# while writing its K/V into the sequence's pool blocks;
# ``decode_step`` is the single-token iteration the token-level
# scheduler (serving/decode.py) compiles to ONE warm executable:
# [max_batch] token rows + the page-table operand, any mix of
# per-sequence lengths, zero steady-state recompiles.
#
# MoE routing at decode uses the oracle path with a no-drop capacity
# (every (token, choice) pair keeps a slot), so a token's output never
# depends on which other sequences share its batch row neighborhood —
# the row-isolation property the admit/retire tests assert.


def init_decode_params(stages, experts, d=16, heads=2, hidden=32,
                       vocab=64, seed=0):
    """:func:`init_params` plus a tied token embedding ``emb``
    [vocab, d] (logits = h @ emb.T)."""
    params = init_params(stages, experts, d=d, heads=heads,
                         hidden=hidden, seed=seed)
    rng = numpy.random.RandomState(seed + 1)
    params["emb"] = jnp.asarray(
        rng.standard_normal((vocab, d)) * 0.25, jnp.float32)
    return params


def _stacked(params):
    """The per-stage leaves (everything but the shared embedding).
    When the param tree carries quantized expert weights (``w1_q`` ...)
    those replace the f32 ``w1``/``w2`` leaves on every decode path."""
    names = ("qkv", "proj", "wr")
    if "w1_q" in params:
        names += ("w1_q", "w1_s", "w2_q", "w2_s")
    else:
        names += ("w1", "w2")
    return {n: params[n] for n in names}


def _moe_dense(p_i, h, k):
    """No-drop oracle MoE for ``h`` [N, d]: capacity covers every
    (token, choice) pair, so routing is per-token independent.
    Quantized expert leaves dispatch to the scaled-accumulate GEMM."""
    if "w1_q" in p_i:
        return moe_reference(
            _expert_ffn_quant,
            {n: p_i[n] for n in ("w1_q", "w1_s", "w2_q", "w2_s")},
            p_i["wr"], h, capacity=h.shape[0] * k, k=k)
    return moe_reference(_expert_ffn,
                         {"w1": p_i["w1"], "w2": p_i["w2"]},
                         p_i["wr"], h, capacity=h.shape[0] * k, k=k)


# -- quantized KV pools -------------------------------------------------------
#
# kv_dtype="int8" swaps each f32 pool array for {"q": int8 pool,
# "s": f32 per-block scales} and every pool write for a sequential
# quantized append: position off==0 resets the block's scale (so the
# bytes a block ends up with depend only on the tokens written into it,
# never on a previous tenant — the determinism prefix-chain dedupe
# relies on), later positions grow the scale monotonically and rescale
# the block's earlier rows when it grows.  With an unchanged scale the
# rescale is exact (round(q * 1) == q), so closed blocks are stable.


def _make_kv_pool(shape, kv_dtype):
    """One per-layer pool: f32 array, or {"q", "s"} leaves for int8
    (``s`` is the [num_blocks, heads] scale array the kernel
    prefetches)."""
    if kv_dtype == "int8":
        return {"q": jnp.zeros(shape, jnp.int8),
                "s": jnp.zeros((shape[0], shape[2]), jnp.float32)}
    return jnp.zeros(shape, jnp.float32)


def _kv_arrays(pool):
    """(data, scales-or-None) view of a pool of either dtype."""
    if isinstance(pool, dict):
        return pool["q"], pool["s"]
    return pool, None


def _append_kv(pool, blk, off, vals, kv_dtype):
    """Write ``vals`` at (blk, off).  f32: the exact ``.at[].set``
    the unquantized path always used.  int8: per-position sequential
    quantized append (see module note above); ``blk``/``off`` may be
    [N] or [B, S] (flattened row-major, so positions within a row stay
    in causal order)."""
    if kv_dtype != "int8":
        return pool.at[blk, off].set(vals)
    q, s = pool["q"], pool["s"]
    blk = blk.reshape(-1)
    off = off.reshape(-1)
    vals = vals.astype(jnp.float32).reshape((blk.shape[0],)
                                            + q.shape[2:])

    def body(t, carry):
        q, s = carry
        b, o, v = blk[t], off[t], vals[t]        # v: [H, hd]
        s_old = jnp.where(o == 0, 0.0, s[b])     # [H]
        s_new = jnp.maximum(s_old,
                            jnp.max(jnp.abs(v), axis=-1) / 127.0)
        s_safe = jnp.where(s_new > 0, s_new, 1.0)
        # ratio == 0 wipes a freshly opened block; ratio == 1 keeps
        # existing rows bit-exact when the scale did not grow
        ratio = jnp.where(s_old > 0, s_old / s_safe, 0.0)
        block = jnp.clip(jnp.round(q[b].astype(jnp.float32)
                                   * ratio[None, :, None]), -127, 127)
        row = jnp.clip(jnp.round(v / s_safe[:, None]), -127, 127)
        block = block.at[o].set(row).astype(jnp.int8)
        return q.at[b].set(block), s.at[b].set(s_new)

    q, s = jax.lax.fori_loop(0, int(blk.shape[0]), body, (q, s))
    return {"q": q, "s": s}


def _prefill_block(p_i, h, heads, k):
    """One dense causal block over the whole prompt; returns the block
    output and this layer's K/V ([T, H, hd]) for the cache."""
    b, t, d = h.shape
    qkv = _rmsnorm(h) @ p_i["qkv"]
    q, kk, vv = (qkv[..., i * d:(i + 1) * d].reshape(b, t, heads,
                                                     d // heads)
                 for i in range(3))
    a = attention_reference(q, kk, vv, causal=True)
    h = h + a.reshape(b, t, d) @ p_i["proj"]
    moe = _moe_dense(p_i, _rmsnorm(h).reshape(b * t, d), k)
    return h + moe.reshape(b, t, d), kk[0], vv[0]


def prefill(params, tokens, length, k_pools, v_pools, block_row, *,
            heads=2, block_size=8, k=1, kv_dtype="f32"):
    """Prompt pass: dense causal forward over ``tokens`` [T_bucket]
    (padded; ``length`` valid), writing each layer's K/V for positions
    < length into the pool blocks named by ``block_row`` [max_blocks].
    Returns (first generated token, k_pools, v_pools).  jit-able; one
    executable per T bucket."""
    t = int(tokens.shape[0])
    h = params["emb"][tokens][None]              # [1, T, d]
    stacked = _stacked(params)
    stages = stacked["qkv"].shape[0]
    pos = jnp.arange(t)
    valid = pos < length
    # invalid positions scatter into physical block 0 — the pool's
    # reserved trash block, never owned by a live sequence
    blk = jnp.where(valid, block_row[pos // block_size], 0)
    off = pos % block_size
    new_k, new_v = [], []
    for i in range(stages):
        p_i = jax.tree.map(lambda p: p[i], stacked)
        h, kk, vv = _prefill_block(p_i, h, heads, k)
        new_k.append(_append_kv(k_pools[i], blk, off, kk, kv_dtype))
        new_v.append(_append_kv(v_pools[i], blk, off, vv, kv_dtype))
    logits = h[0, length - 1] @ params["emb"].T
    token = jnp.argmax(logits).astype(jnp.int32)
    return token, tuple(new_k), tuple(new_v)


def prefill_chunk(params, tokens, start, length, k_pools, v_pools,
                  block_row, *, heads=2, block_size=8, k=1,
                  kv_dtype="f32"):
    """One fixed-size prefill chunk: positions ``start .. start+C-1``
    of a prompt whose earlier K/V — resident prefix blocks reused from
    the pool plus chunks already executed — are read back THROUGH the
    page-table row, not recomputed.  Per-layer: write this chunk's K/V
    into its pool slots, then ragged paged attention with per-query
    causal lengths (znicz.paged_attention.paged_prefill_attention).

    Static shapes: [C] tokens, scalar start/length — ONE executable
    covers every chunk of every prompt, which is what lets the
    scheduler interleave prefill chunks with decode steps instead of
    stalling the batch on a monolithic ladder call.  Returns (token,
    pools); the token is the first generated token and is only
    meaningful on the final chunk (``start + C >= length``).
    """
    from ..paged_attention import paged_prefill_attention
    c = int(tokens.shape[0])
    h = params["emb"][tokens][None]              # [1, C, d]
    stacked = _stacked(params)
    stages = stacked["qkv"].shape[0]
    d = h.shape[-1]
    hd = d // heads
    pos = start + jnp.arange(c)
    valid = pos < length
    # invalid positions scatter into the reserved trash block
    blk = jnp.where(valid, block_row[pos // block_size], 0)
    off = pos % block_size
    k_pools, v_pools = list(k_pools), list(v_pools)
    for i in range(stages):
        p_i = jax.tree.map(lambda p: p[i], stacked)
        qkv = _rmsnorm(h) @ p_i["qkv"]           # [1, C, 3d]
        q, kk, vv = (qkv[..., j * d:(j + 1) * d].reshape(1, c, heads,
                                                         hd)
                     for j in range(3))
        k_pools[i] = _append_kv(k_pools[i], blk, off, kk[0], kv_dtype)
        v_pools[i] = _append_kv(v_pools[i], blk, off, vv[0], kv_dtype)
        kd, ks = _kv_arrays(k_pools[i])
        vd, vs = _kv_arrays(v_pools[i])
        a = paged_prefill_attention(q[0], kd, vd,
                                    block_row, start, length,
                                    scale=1.0 / math.sqrt(hd),
                                    k_scales=ks, v_scales=vs)
        h = h + a.reshape(1, c, d) @ p_i["proj"]
        moe = _moe_dense(p_i, _rmsnorm(h).reshape(c, d), k)
        h = h + moe.reshape(1, c, d)
    last = jnp.clip(length - 1 - start, 0, c - 1)
    logits = h[0, last] @ params["emb"].T
    return (jnp.argmax(logits).astype(jnp.int32), tuple(k_pools),
            tuple(v_pools))


def _decode_block(p_i, h, k_pool_i, v_pool_i, page_table, lengths,
                  blk, off, heads, k, kv_dtype="f32"):
    """One single-token block: write this token's K/V into its pool
    slot, then ragged paged attention over the whole cached history
    (lengths + 1 includes the token just written)."""
    from ..paged_attention import paged_attention
    b, d = h.shape
    hd = d // heads
    qkv = _rmsnorm(h) @ p_i["qkv"]               # [B, 3d]
    q, kk, vv = (qkv[:, i * d:(i + 1) * d].reshape(b, heads, hd)
                 for i in range(3))
    k_pool_i = _append_kv(k_pool_i, blk, off, kk, kv_dtype)
    v_pool_i = _append_kv(v_pool_i, blk, off, vv, kv_dtype)
    kd, ks = _kv_arrays(k_pool_i)
    vd, vs = _kv_arrays(v_pool_i)
    a = paged_attention(q, kd, vd, page_table, lengths + 1,
                        scale=1.0 / math.sqrt(hd),
                        k_scales=ks, v_scales=vs)
    h = h + a.reshape(b, d) @ p_i["proj"]
    return h + _moe_dense(p_i, _rmsnorm(h), k), k_pool_i, v_pool_i


def decode_step(params, k_pools, v_pools, page_table, lengths, tokens,
                *, heads=2, block_size=8, k=1, kv_dtype="f32",
                with_logits=False):
    """One token for every row: embed ``tokens`` [B], write each row's
    K/V at position ``lengths[row]``, attend through the page table,
    return (next greedy tokens [B], k_pools, v_pools).

    Static shapes throughout — max-batch rows and the [B, max_blocks]
    page table — so the serving scheduler compiles this ONCE and runs
    arbitrary admit/retire mixes against the same executable.  Padding
    rows (lengths == 0 with an all-zero table row) write into the trash
    block and produce ignored tokens.
    """
    b = int(tokens.shape[0])
    h = params["emb"][tokens]                    # [B, d]
    stacked = _stacked(params)
    stages = stacked["qkv"].shape[0]
    rows = jnp.arange(b)
    blk = page_table[rows, lengths // block_size]
    off = lengths % block_size
    k_pools, v_pools = list(k_pools), list(v_pools)
    for i in range(stages):
        p_i = jax.tree.map(lambda p: p[i], stacked)
        h, k_pools[i], v_pools[i] = _decode_block(
            p_i, h, k_pools[i], v_pools[i], page_table, lengths, blk,
            off, heads, k, kv_dtype=kv_dtype)
    logits = h @ params["emb"].T                 # [B, V]
    out = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if with_logits:
        return out, tuple(k_pools), tuple(v_pools), logits
    return out, tuple(k_pools), tuple(v_pools)


def _verify_block(p_i, h, k_pool_i, v_pool_i, page_table, lengths,
                  blk, off, heads, k, kv_dtype="f32"):
    """One multi-token block of the speculative verify pass: write all
    S fed tokens' K/V into their pool slots, then ragged verify
    attention — per-position causal lengths keep query ``i`` blind to
    the drafts after it (znicz.paged_attention.paged_verify_attention).
    """
    from ..paged_attention import paged_verify_attention
    b, s, d = h.shape
    hd = d // heads
    qkv = _rmsnorm(h) @ p_i["qkv"]               # [B, S, 3d]
    q, kk, vv = (qkv[..., i * d:(i + 1) * d].reshape(b, s, heads, hd)
                 for i in range(3))
    k_pool_i = _append_kv(k_pool_i, blk, off, kk, kv_dtype)
    v_pool_i = _append_kv(v_pool_i, blk, off, vv, kv_dtype)
    kd, ks = _kv_arrays(k_pool_i)
    vd, vs = _kv_arrays(v_pool_i)
    a = paged_verify_attention(q, kd, vd, page_table,
                               lengths, scale=1.0 / math.sqrt(hd),
                               k_scales=ks, v_scales=vs)
    h = h + a.reshape(b, s, d) @ p_i["proj"]
    moe = _moe_dense(p_i, _rmsnorm(h).reshape(b * s, d), k)
    return h + moe.reshape(b, s, d), k_pool_i, v_pool_i


def verify_step(params, k_pools, v_pools, page_table, lengths, tokens,
                *, heads=2, block_size=8, k=1, kv_dtype="f32"):
    """Speculative verify: ``tokens`` [B, S] is each row's next input
    plus its S-1 draft tokens.  Every position is written at
    ``lengths[row] + i`` and attended with causal length
    ``lengths[row] + i + 1``, so ``out[:, i]`` is the target's greedy
    next token given the history plus fed tokens ``0 .. i`` — exactly
    the token plain decode would emit at that step when the drafts
    before it are all correct.  One executable per (B, S) — the ragged
    kernel absorbs any mix of per-row lengths.

    Writes past a row's page-table capacity scatter into the trash
    block (only ever possible for draft positions past the row's
    remaining token budget, whose outputs the scheduler discards).
    The MoE stays the no-drop oracle over the flattened [B*S] tokens,
    so rows remain isolated from each other AND positions from their
    own rejected tails.
    """
    b, s = int(tokens.shape[0]), int(tokens.shape[1])
    h = params["emb"][tokens]                    # [B, S, d]
    stacked = _stacked(params)
    stages = stacked["qkv"].shape[0]
    nb = page_table.shape[1]
    rows = jnp.arange(b)[:, None]
    pos = lengths[:, None] + jnp.arange(s)[None, :]
    blk = jnp.where(pos < nb * block_size,
                    page_table[rows, jnp.minimum(pos // block_size,
                                                 nb - 1)], 0)
    off = pos % block_size
    k_pools, v_pools = list(k_pools), list(v_pools)
    for i in range(stages):
        p_i = jax.tree.map(lambda p: p[i], stacked)
        h, k_pools[i], v_pools[i] = _verify_block(
            p_i, h, k_pools[i], v_pools[i], page_table, lengths, blk,
            off, heads, k, kv_dtype=kv_dtype)
    logits = h @ params["emb"].T                 # [B, S, V]
    return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
            tuple(k_pools), tuple(v_pools))


def _reference_hidden(params, tokens, heads, k):
    """The dense causal forward over ``tokens`` [T] -> [1, T, d]."""
    stacked = _stacked(params)
    h = params["emb"][jnp.asarray(tokens, jnp.int32)][None]
    for i in range(stacked["qkv"].shape[0]):
        p_i = jax.tree.map(lambda p: p[i], stacked)
        h, _, _ = _prefill_block(p_i, h, heads, k)
    return h


def reference_logits(params, tokens, heads=2, k=1):
    """Teacher-forced face of :func:`generate_reference`: the dense
    causal forward over ``tokens`` [T] as logits [T, vocab], row ``t``
    being what the oracle sees after ``tokens[:t + 1]``.  Attention is
    causal and the MoE routes per token, so a row never depends on
    later tokens: one jitted call over a padded sequence checks every
    generated token and gives the top-2 margin where one differs."""
    return _reference_hidden(params, tokens, heads, k)[0] \
        @ params["emb"].T


def generate_reference(params, prompt, n_new, heads=2, k=1):
    """Cache-free greedy oracle: rerun the full dense causal forward
    over the whole history for every generated token.  O(T^2) per
    token — tests only."""
    tokens = [int(t) for t in prompt]
    out = []
    for _ in range(n_new):
        h = _reference_hidden(params, tokens, heads, k)
        logits = h[0, -1] @ params["emb"].T
        nxt = int(jnp.argmax(logits))
        out.append(nxt)
        tokens.append(nxt)
    return out


class FlagshipDecodeModel:
    """The decode-serving adapter: flagship params + the jit-able
    prefill / decode-step closures the token-level scheduler
    (serving/decode.py) compiles.  ``kind = "decode"`` is what
    ModelRegistry.add dispatches on."""

    kind = "decode"
    #: KV-cache precisions this model's factories accept (the
    #: scheduler checks this before forwarding a non-default kv_dtype)
    kv_dtypes = ("f32", "int8")

    def __init__(self, params=None, *, stages=2, experts=2, d=16,
                 heads=2, hidden=32, vocab=64, k=1, seed=0,
                 kv_dtype="f32", weight_dtype="f32"):
        if params is None:
            params = init_decode_params(stages, experts, d=d,
                                        heads=heads, hidden=hidden,
                                        vocab=vocab, seed=seed)
        if kv_dtype not in self.kv_dtypes:
            raise ValueError("kv_dtype=%r not in %r"
                             % (kv_dtype, self.kv_dtypes))
        if weight_dtype != "f32":
            params = dict(params)
            for name in ("w1", "w2"):
                q, s = _quantize_weight_stack(params[name],
                                              weight_dtype)
                params[name + "_q"], params[name + "_s"] = q, s
        self.kv_dtype = kv_dtype
        self.weight_dtype = weight_dtype
        self.params = params
        self.heads = int(heads)
        self.k = int(k)
        self.layers = int(params["qkv"].shape[0])
        self.vocab = int(params["emb"].shape[0])
        self.d = int(params["emb"].shape[1])
        if self.d % self.heads:
            raise ValueError("d=%d not divisible by heads=%d"
                             % (self.d, self.heads))
        self.head_dim = self.d // self.heads
        self._draft_table = None

    def _kv(self, kv_dtype):
        return self.kv_dtype if kv_dtype is None else kv_dtype

    def make_pools(self, num_blocks, block_size, kv_dtype=None):
        """Fresh zeroed per-layer K and V pools
        ([num_blocks, block_size, H, hd] x layers); int8 pools are
        {"q", "s"} leaves per layer."""
        dt = self._kv(kv_dtype)
        shape = (int(num_blocks), int(block_size), self.heads,
                 self.head_dim)
        k_pools = tuple(_make_kv_pool(shape, dt)
                        for _ in range(self.layers))
        v_pools = tuple(_make_kv_pool(shape, dt)
                        for _ in range(self.layers))
        return k_pools, v_pools

    def prefill_fn(self, block_size, kv_dtype=None):
        """(tokens, length, k_pools, v_pools, block_row) ->
        (first token, pools) — close over the static geometry."""
        params, heads, k = self.params, self.heads, self.k
        dt = self._kv(kv_dtype)

        def fn(tokens, length, k_pools, v_pools, block_row):
            return prefill(params, tokens, length, k_pools, v_pools,
                           block_row, heads=heads,
                           block_size=block_size, k=k, kv_dtype=dt)
        return fn

    def prefill_chunk_fn(self, block_size, kv_dtype=None):
        """(tokens[C], start, length, k_pools, v_pools, block_row) ->
        (token, pools) — the one-executable chunked-prefill step."""
        params, heads, k = self.params, self.heads, self.k
        dt = self._kv(kv_dtype)

        def fn(tokens, start, length, k_pools, v_pools, block_row):
            return prefill_chunk(params, tokens, start, length,
                                 k_pools, v_pools, block_row,
                                 heads=heads, block_size=block_size,
                                 k=k, kv_dtype=dt)
        return fn

    def decode_fn(self, block_size, kv_dtype=None):
        """(k_pools, v_pools, page_table, lengths, tokens) ->
        (next tokens, pools)."""
        params, heads, k = self.params, self.heads, self.k
        dt = self._kv(kv_dtype)

        def fn(k_pools, v_pools, page_table, lengths, tokens):
            return decode_step(params, k_pools, v_pools, page_table,
                               lengths, tokens, heads=heads,
                               block_size=block_size, k=k, kv_dtype=dt)
        return fn

    def logits_fn(self, block_size, kv_dtype=None):
        """Like :meth:`decode_fn` but also returns the [B, V] logits —
        the probe/bench hook for measuring quantization error against
        the f32 oracle."""
        params, heads, k = self.params, self.heads, self.k
        dt = self._kv(kv_dtype)

        def fn(k_pools, v_pools, page_table, lengths, tokens):
            return decode_step(params, k_pools, v_pools, page_table,
                               lengths, tokens, heads=heads,
                               block_size=block_size, k=k, kv_dtype=dt,
                               with_logits=True)
        return fn

    def _unigram_table(self):
        """The drafter: a [vocab] next-token table distilled from the
        target by running it on every single-token prompt (a
        context-free student of the teacher — the cheapest drafter
        that still agrees with the target more often than chance).
        Computed once, host-side, on first use."""
        if self._draft_table is None:
            h = self.params["emb"][jnp.arange(self.vocab)][:, None]
            stacked = _stacked(self.params)
            for i in range(self.layers):
                p_i = jax.tree.map(lambda p: p[i], stacked)
                h, _, _ = _prefill_block(p_i, h, self.heads, self.k)
            logits = h[:, 0] @ self.params["emb"].T
            self._draft_table = jnp.argmax(
                logits, axis=-1).astype(jnp.int32)
        return self._draft_table

    def draft_fn(self, block_size, depth, kv_dtype=None):
        """(k_pools, v_pools, page_table, lengths, tokens[B]) ->
        draft tokens [B, depth].  Pure reads — drafting never writes
        the pools; acceptance is decided by the verify pass."""
        table = self._unigram_table()
        depth = int(depth)

        def fn(k_pools, v_pools, page_table, lengths, tokens):
            t = tokens
            outs = []
            for _ in range(depth):
                t = table[t]
                outs.append(t)
            return jnp.stack(outs, axis=1)
        return fn

    def verify_fn(self, block_size, depth, kv_dtype=None):
        """(k_pools, v_pools, page_table, lengths, tokens[B, depth+1])
        -> (out tokens [B, depth+1], pools) — the one-pass multi-token
        verify the scheduler compiles once per speculation depth."""
        params, heads, k = self.params, self.heads, self.k
        dt = self._kv(kv_dtype)

        def fn(k_pools, v_pools, page_table, lengths, tokens):
            return verify_step(params, k_pools, v_pools, page_table,
                               lengths, tokens, heads=heads,
                               block_size=block_size, k=k, kv_dtype=dt)
        return fn


def train_step(params, x, target, mesh, lr=0.05, **kwargs):
    """One fused SGD step of the full composition; jit-able."""
    def loss_fn(p):
        y = flagship_apply(p, x, mesh, **kwargs)
        return ((y - target) ** 2).mean()

    loss, grads = jax.value_and_grad(loss_fn)(params)
    return loss, jax.tree.map(lambda p, g: p - lr * g, params, grads)
