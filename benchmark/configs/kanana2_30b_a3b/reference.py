"""Plain reference of the ``kanana2_30b_a3b`` configuration: the decoder
that ``config.json`` describes (``model_type: deepseek_v3``: multi-head
latent attention without query compression, one leading dense layer,
then sigmoid-routed experts with shared experts) in straightforward
``jax.numpy`` float32 — no kernels, no sort, no cache; attention as
explicit scores, computed in blocks of queries so that 8,192 x 8,192 x 32
fits; the experts as a loop with a dense mask.  It imports nothing from
the program.

    logits = forward(config, params, ids)              # [B, S, V] float32
    loss   = token_loss(logits, labels)                # mean cross-entropy
    loss, grads = loss_and_grads(config, params, ids, labels)
    params, m, v = adamw_steps(config, params, ids, labels, n)

``params`` is a list of dictionaries, one a unit, in the order of the
layers: ``{"weights"}`` (embedding [V, d]); per layer an attention
dictionary ``{"norm", "wq", "wkva", "kv_norm", "wkvb", "wo"}`` and either
``{"norm", "gate", "up", "down"}`` or ``{"norm", "router", "router_bias",
"experts_gate_up" [E, d, 2f], "experts_down" [E, f, d], "shared_gate",
"shared_up", "shared_down"}``; last ``{"norm", "weights"}`` (head [d, V]).

``config`` keys read: the published ones, and the cut: ``n_layers``
(depth run), ``n_routed_experts`` (experts HELD here, ``experts_offset``
the first), ``router_width`` (the router's published 128 outputs),
``vocab_size`` (the slice).

Departures from the published description, each also where it happens:
(1) of the ``router_width`` experts only the held ones are computed: what
the absent ones would add is left out, here as in the program (one chip
of an expert-parallel group; the all-to-all is not modelled); (2) the
vocabulary is a slice: embedding, head and loss are over ``vocab_size``
ids; (3) ``e_score_correction_bias`` is whatever ``router_bias`` holds
and is never updated; (4) no attention or padding mask beyond causality.

``precision``: ``"highest"`` is the mathematics (six bf16 passes a
product on a TPU).  ``"default"`` is the arithmetic ``config.json``
states: matrix operands rounded to bfloat16, sums float32, the
activations between blocks rounded to bfloat16; router, norm statistics,
rotary angles and loss float32.  ``"float8"`` rounds matrix operands to
float8_e4m3 instead: the nearest precision BELOW the stated one, which
the benchmark's comparison has to refuse.
"""

import math

import jax
import jax.numpy as jnp

#: queries per block of explicit scores
QUERY_BLOCK = 1024


def _operand(a, precision):
    if precision == "highest":
        return a
    low = jnp.bfloat16 if precision == "default" else jnp.float8_e4m3fn
    return a.astype(low).astype(jnp.float32)


def _mm(spec, a, b, precision):
    """einsum of float32 operands rounded as ``precision`` says, float32
    sums."""
    return jnp.einsum(spec, _operand(a, precision), _operand(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def _act(x, precision):
    """Activations between blocks: bfloat16 in the stated arithmetic."""
    if precision == "highest":
        return x
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def rope(x, theta):
    """``rope_interleave``: the pairs ``(2i, 2i+1)`` of the last axis of
    ``x`` [..., S, D] rotate by ``position * theta ** (-2i / D)``; no
    scaling (``rope_scaling: null``)."""
    s, d = x.shape[-2], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq
    even, odd = x[..., 0::2], x[..., 1::2]
    rotated = jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                         even * jnp.sin(angle) + odd * jnp.cos(angle)], -1)
    return rotated.reshape(x.shape)


def attention(config, p, x, precision):
    """Multi-head latent attention with residual: x [B, S, d]."""
    heads = config["num_attention_heads"]
    nope, rot = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vdim, rank = config["v_head_dim"], config["kv_lora_rank"]
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    b, s, d = x.shape
    h = _act(rms_norm(x, p["norm"], eps), precision)
    q = _act(_mm("bsd,dk->bsk", h, p["wq"], precision), precision)
    q = q.reshape(b, s, heads, nope + rot)
    ckv = _mm("bsd,dk->bsk", h, p["wkva"], precision)
    latent = _act(rms_norm(_act(ckv[..., :rank], precision), p["kv_norm"],
                           eps), precision)
    # ONE rope key a token, shared by every head
    k_rope = _act(rope(ckv[..., rank:], theta), precision)     # [B, S, rot]
    kv = _act(_mm("bsr,rk->bsk", latent, p["wkvb"], precision), precision)
    kv = kv.reshape(b, s, heads, nope + vdim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_nope = q[..., :nope]
    q_rope = _act(rope(q[..., nope:].transpose(0, 2, 1, 3), theta),
                  precision).transpose(0, 2, 1, 3)
    scale = 1.0 / math.sqrt(nope + rot)

    # blocks of queries against all keys, one block at a time (lax.map:
    # one copy of the block in the program, its scores not kept)
    size = min(QUERY_BLOCK, s)
    assert s % size == 0, (s, size)

    @jax.checkpoint
    def block(start):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, start, size, axis=1)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, start, size, axis=1)
        scores = (_mm("bqhd,bkhd->bhqk", qn, k_nope, precision)
                  + _mm("bqhd,bkd->bhqk", qr, k_rope, precision)) * scale
        future = jnp.arange(s)[None, :] > (start + jnp.arange(size))[:, None]
        prob = jax.nn.softmax(jnp.where(future, -jnp.inf, scores), axis=-1)
        return _mm("bhqk,bkhd->bqhd", prob, v, precision)
    outs = jax.lax.map(block, jnp.arange(0, s, size))   # [blocks, B, size,
    out = jnp.moveaxis(outs, 0, 1).reshape(b, s, heads, vdim)   # H, vdim]
    out = _act(out, precision)
    y = _mm("bsk,kd->bsd", out.reshape(b, s, heads * vdim), p["wo"],
            precision)
    return _act(x + y, precision)


def gated_mlp(h, gate, up, down, precision, rounded=False):
    """``(silu(h gate) * (h up)) down``.  ``rounded``: the two inner
    products come back in bfloat16 in the stated arithmetic (the routed
    experts' grouped product returns its operands' dtype)."""
    g = _mm("...d,df->...f", h, gate, precision)
    u = _mm("...d,df->...f", h, up, precision)
    if rounded:
        g, u = _act(g, precision), _act(u, precision)
    return _mm("...f,fd->...d", _act(jax.nn.silu(g) * u, precision), down,
               precision)


def dense_mlp(config, p, x, precision):
    h = _act(rms_norm(x, p["norm"], config["rms_norm_eps"]), precision)
    return _act(x + gated_mlp(h, p["gate"], p["up"], p["down"], precision),
                precision)


def route(config, p, h):
    """Dense routing weights [..., router_width], zero where an expert
    was not chosen: ``s = sigmoid(h W_r)`` in float32; the
    ``num_experts_per_tok`` largest ``s + b`` are chosen (``noaux_tc``;
    ``n_group = topk_group = 1``: no group limit); the weights are
    ``routed_scaling_factor * s / sum of the chosen s``
    (``norm_topk_prob``)."""
    s = jax.nn.sigmoid(jnp.einsum("...d,de->...e", h, p["router"],
                                  precision=jax.lax.Precision.HIGHEST))
    k = config["num_experts_per_tok"]
    kth = jax.lax.top_k(s + p["router_bias"], k)[0][..., -1:]
    chosen = (s + p["router_bias"]) >= kth
    w = jnp.where(chosen, s, 0.0)
    if config.get("norm_topk_prob", True):
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return w * config["routed_scaling_factor"]


def expert_layer(config, p, x, precision):
    f = config["moe_intermediate_size"]
    h = _act(rms_norm(x, p["norm"], config["rms_norm_eps"]), precision)
    w = route(config, p, h)
    # departure (1): only the experts held here, one after the other
    held, first = config["n_routed_experts"], config.get("experts_offset", 0)

    def add_expert(y, expert):
        gate_up, down, weight = expert
        out = gated_mlp(h, gate_up[:, :f], gate_up[:, f:], down, precision,
                        rounded=True)
        # the program rounds each expert's output to bfloat16 before the
        # weighted sum (the grouped product's result dtype)
        return y + weight[..., None] * _act(out, precision), None
    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (
        p["experts_gate_up"][:held], p["experts_down"][:held],
        jnp.moveaxis(w[..., first:first + held], -1, 0)))
    if config.get("n_shared_experts"):
        y = y + gated_mlp(h, p["shared_gate"], p["shared_up"],
                          p["shared_down"], precision)
    return _act(x + y, precision)


def forward(config, params, ids, precision="highest"):
    """Logits [B, S, vocab_size] float32 of token ids [B, S].  (Each
    layer is a ``jax.checkpoint``: under differentiation its activations
    are recomputed, which changes the memory and not the numbers.)"""
    def layer_of(function):
        return jax.checkpoint(lambda p, x: function(config, p, x, precision))
    params = iter(params)
    x = _act(next(params)["weights"][ids], precision)
    for layer in range(config["n_layers"]):
        x = layer_of(attention)(next(params), x)
        if layer < config["first_k_dense_replace"]:
            x = layer_of(dense_mlp)(next(params), x)
        else:
            x = layer_of(expert_layer)(next(params), x)
    head = next(params)
    h = _act(rms_norm(x, head["norm"], config["rms_norm_eps"]), precision)
    return _mm("bsd,dv->bsv", h, head["weights"], precision)


def token_loss(logits, labels):
    """Mean next-token cross-entropy: ``labels`` [B, S] are the ids that
    follow each position."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def loss_and_grads(config, params, ids, labels, precision="highest"):
    """The mean loss over the batch and its gradient, a sequence at a
    time so that it fits: sequences are independent and equally long, so
    the means of their losses and gradients are the batch's."""
    def add_sequence(total, sequence):
        one = jax.value_and_grad(lambda p: token_loss(forward(
            config, p, sequence[0][None], precision), sequence[1][None]))(
            params)
        return jax.tree.map(jnp.add, total, one), None
    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params))
    total, _ = jax.lax.scan(add_sequence, zero, (ids, labels))
    return jax.tree.map(lambda a: a / ids.shape[0], total)


def adamw_steps(config, params, ids, labels, steps, precision="highest",
                state=None):
    """``steps`` steps of AdamW (Loshchilov & Hutter 2019) on the one
    batch with ``config["solver"]``: ``m = b1 m + (1 - b1) g``; ``v = b2
    v + (1 - b2) g^2``; ``w -= lr * (m / (1 - b1^t) / (sqrt(v / (1 -
    b2^t)) + eps) + decay * w)``, the decay on matrices only (tensors of
    two or more axes).  From zero moments, or from ``state = (m, v,
    steps already made)``.  Returns (parameters, first moments, second
    moments)."""
    sol = config["solver"]
    lr, b1, b2 = sol["learning_rate"], sol["beta1"], sol["beta2"]
    eps, decay = sol["epsilon"], sol["weight_decay"]
    if state is None:
        state = (jax.tree.map(jnp.zeros_like, params),
                 jax.tree.map(jnp.zeros_like, params), 0)
    m, v, done = state
    for step in range(steps):
        t = jnp.asarray(done + step + 1, jnp.float32)
        _, grads = loss_and_grads(config, params, ids, labels, precision)
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)

        def update(w, m, v):
            change = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t))
                                            + eps)
            if w.ndim >= 2:
                change = change + decay * w
            return w - lr * change
        params = jax.tree.map(update, params, m, v)
    return params, m, v
