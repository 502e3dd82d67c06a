"""Plain reference of the ``mellum2_12b_a2p5b`` configuration: the decoder
that ``config.json`` describes (``model_type: mellum``: grouped-query
attention with per-head q/k norms in every layer, three layers inside a
sliding window to one full layer whose rotary angles are YaRN's, every
feed-forward a softmax-routed expert layer without a shared expert) in
straightforward ``jax.numpy`` float32 — no kernels, no sort, no cache;
attention as explicit scores with the key-value heads repeated for the
query heads that read them and the band as a mask, computed in blocks of
queries so that 8,192 x 8,192 x 32 fits; the experts as a loop with a
dense mask.  It imports nothing from the program.

    logits = forward(config, params, ids)              # [B, S, V] float32
    loss   = token_loss(logits, labels)                # mean cross-entropy
    loss, grads = loss_and_grads(config, params, ids, labels)
    params, m, v = adamw_steps(config, params, ids, labels, n)
    loads = router_loads(config, params, ids)          # [expert layers, E]

The layer equations (``x`` [B, S, d] the residual stream, ``h =
RMSNorm(x)``, every published layer an attention then a feed-forward):

- attention: ``q = h W_q`` as ``num_attention_heads`` heads, ``k = h
  W_k``, ``v = h W_v`` as ``num_key_value_heads`` heads of ``head_dim``;
  ``q``, ``k`` RMS-normalised over each head with one learned weight
  each; rotary embedding over the whole head with the pairs ``(i, i +
  head_dim / 2)`` and the angles of the layer kind's ``rope_parameters``
  group (``rope_frequencies``); causal softmax of ``q . k /
  sqrt(head_dim)``, query head ``n`` reading key-value head ``n //
  group``; in a ``sliding_attention`` layer position ``i`` sees the keys
  in ``(i - sliding_window, i]``; ``x + concat(heads) W_o``;
- angles, ``rope_type: default``: pair ``i`` turns by ``position *
  theta ** (-2i / d)``.  ``yarn`` (Peng et al., arXiv:2309.00071, as the
  family's code reads the group's keys): ``extrap_i = theta ** (-2i /
  d)``, ``interp_i = extrap_i / factor``; ``c(r) = d ln(original_max /
  (2 pi r)) / (2 ln theta)``; ``low = max(floor(c(beta_fast)), 0)``,
  ``high = min(ceil(c(beta_slow)), d - 1)``; ``ramp_i = clip((i - low) /
  (high - low), 0, 1)``; ``inv_freq_i = interp_i ramp_i + extrap_i (1 -
  ramp_i)``; cos and sin multiplied by ``attention_factor``, so that a
  full layer's scores carry its square; at every length;
- feed-forward (``mlp_layer_types: sparse``): ``p = softmax(h W_r)``
  over all ``router_width`` experts in float32; the
  ``num_experts_per_tok`` largest ``p + b`` chosen; ``w = p_chosen /
  (sum p_chosen + norm_topk_eps)``; ``x + sum_e w_e (silu(h G_e) * (h
  U_e)) D_e`` over the chosen experts held here (``route``,
  ``expert_layer``).

``params`` is a list of dictionaries, one a unit, in the order of the
layers: ``{"weights"}`` (embedding [V, d]); per layer ``{"norm", "wq",
"wk", "wv", "q_norm", "k_norm", "wo"}`` and ``{"norm", "router",
"router_bias", "experts_gate_up" [E, d, 2f], "experts_down" [E, f, d]}``;
last ``{"norm", "weights"}`` (head [d, V]).

``config`` keys read: the published ones, and the cut: ``layer_types``
(the depth run), ``num_experts`` (experts HELD here, ``experts_offset``
the first), ``router_width`` (the router's published 64 outputs),
``vocab_size`` (the slice).

Departures from the published description, each also where it happens:
(1) of the ``router_width`` experts only the held ones are computed: what
the absent ones would add is left out, here as in the program (one chip
of an expert-parallel group; the all-to-all is not modelled); (2) the
vocabulary is a slice: embedding, head and loss are over ``vocab_size``
ids; (3) THE PUBLISHED ROUTER HAS NO BIAS; the program's expert block
always holds one (``router_bias``, used for the choice only), zero at
the start in this configuration; the reference adds whatever
``router_bias`` holds to the choice (the harness's probe parameters draw
one), and with ``bias_update_rate`` moves it a train step against its
expert's load by the error itself (``bias_update_rule`` ``proportional``;
``adamw_steps``), the load counted over this chip's
tokens alone: the one balancing means the block has, which
``config.json`` ``assumed.router_bias`` gives the reason and the
measurements for; (4) no attention or padding mask beyond
causality and the window; (5) the multi-token-prediction head that the
catalog's ``described_as`` names is left out: ``config.json`` holds
nothing of it; (6) with ``train_router`` false THE ROUTER GETS NO
GRADIENT: the routing weights are constants of the backward pass.  A
router's gradient is made of the outputs of all the experts a token
chose; this chip has those of its own experts alone (departure 1), and a
router taught by them alone learns that the absent experts add nothing
and sends every token to the held ones, which no chip of the whole job
sees.

``precision``: ``"highest"`` is the mathematics (six bf16 passes a
product on a TPU).  ``"default"`` is the arithmetic ``config.json``
states: matrix operands rounded to bfloat16, sums float32, the
activations between blocks and between a block's products rounded to
bfloat16; router, norm statistics, rotary angles and loss float32.
``"float8"`` rounds matrix operands to float8_e4m3 instead: the nearest
precision BELOW the stated one, which the benchmark's comparison has to
refuse.
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
    """Activations between products: bfloat16 in the stated arithmetic."""
    if precision == "highest":
        return x
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def rope_frequencies(group, d):
    """(``inv_freq`` [d / 2] float32, the factor on cos and sin) of one
    ``rope_parameters`` group for heads of ``d``."""
    theta = float(group["rope_theta"])
    pair = jnp.arange(0, d, 2, dtype=jnp.float32) / 2           # i
    extrap = theta ** (-2 * pair / d)
    if group["rope_type"] == "default":
        return extrap, 1.0
    assert group["rope_type"] == "yarn", group["rope_type"]
    factor = float(group["factor"])
    interp = extrap / factor

    def c(turns):
        return d * math.log(group["original_max_position_embeddings"]
                            / (2 * math.pi * turns)) / (2 * math.log(theta))
    low = max(math.floor(c(group["beta_fast"])), 0)
    high = min(math.ceil(c(group["beta_slow"])), d - 1)
    ramp = jnp.clip((pair - low) / max(high - low, 0.001), 0.0, 1.0)
    return interp * ramp + extrap * (1 - ramp), group["attention_factor"]


def rope(x, group):
    """The pairs ``(i, i + D/2)`` of the last axis of ``x`` [B, S, H, D]
    rotate by ``position * inv_freq_i``; cos and sin carry the group's
    factor."""
    s, d = x.shape[1], x.shape[-1]
    freq, gain = rope_frequencies(group, d)
    angle = (jnp.arange(s, dtype=jnp.float32)[:, None] * freq)[:, None, :]
    cos, sin = jnp.cos(angle) * gain, jnp.sin(angle) * gain
    first, second = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([first * cos - second * sin,
                            first * sin + second * cos], axis=-1)


def attention(config, kind, p, x, precision):
    """Grouped-query attention of layer kind ``kind`` with residual: x
    [B, S, d]."""
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    dim, eps = config["head_dim"], config["rms_norm_eps"]
    group = config["rope_parameters"][kind]
    window = config["sliding_window"] if (
        kind == "sliding_attention"
        and config.get("use_sliding_window", True)) else None
    b, s, d = x.shape
    h = _act(rms_norm(x, p["norm"], eps), precision)

    def project(name, n):
        return _act(_mm("bsd,dk->bsk", h, p[name], precision),
                    precision).reshape(b, s, n, dim)

    def normed(t, weight):
        return _act(rope(_act(rms_norm(t, weight, eps), precision), group),
                    precision)
    q = normed(project("wq", heads), p["q_norm"])
    # every key-value head repeated for the query heads that read it
    repeat = heads // kv_heads
    k = jnp.repeat(normed(project("wk", kv_heads), p["k_norm"]), repeat,
                   axis=2)
    v = jnp.repeat(project("wv", kv_heads), repeat, axis=2)
    scale = 1.0 / math.sqrt(dim)

    # blocks of queries against all keys, one block at a time (lax.map:
    # one copy of the block in the program, its scores not kept)
    size = min(QUERY_BLOCK, s)
    assert s % size == 0, (s, size)

    @jax.checkpoint
    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, size, axis=1)
        scores = _mm("bqhd,bkhd->bhqk", qb, k, precision) * scale
        query = (start + jnp.arange(size))[:, None]
        key = jnp.arange(s)[None, :]
        unseen = key > query                    # the future
        if window is not None:                  # and what the band left
            unseen = unseen | (key <= query - window)
        prob = jax.nn.softmax(jnp.where(unseen, -jnp.inf, scores), axis=-1)
        return _mm("bhqk,bkhd->bqhd", prob, v, precision)
    outs = jax.lax.map(block, jnp.arange(0, s, size))   # [blocks, B, size,
    out = jnp.moveaxis(outs, 0, 1).reshape(b, s, heads * dim)   # H, dim]
    y = _mm("bsk,kd->bsd", _act(out, precision), p["wo"], precision)
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


def route(config, p, h, chosen_only=False):
    """Dense routing weights [..., router_width], zero where an expert
    was not chosen: ``s = softmax(h W_r)`` over all the experts in
    float32 (``scoring_func``; ``sigmoid``: each expert alone); the
    ``num_experts_per_tok`` largest ``s + b`` are chosen (departure 3:
    the bias decides the choice only, and is zero as published); the
    weights are ``routed_scaling_factor * s / (sum of the chosen s +
    norm_topk_eps)`` (``norm_topk_prob``).  ``chosen_only``: the choice
    itself, as a mask."""
    logits = jnp.einsum("...d,de->...e", h, p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.softmax(logits, axis=-1) \
        if config["scoring_func"] == "softmax" else jax.nn.sigmoid(logits)
    biased = s + p["router_bias"]
    chosen = biased >= jax.lax.top_k(
        biased, config["num_experts_per_tok"])[0][..., -1:]
    if chosen_only:
        return chosen
    w = jnp.where(chosen, s, 0.0)
    if config["norm_topk_prob"]:
        w = w / (w.sum(axis=-1, keepdims=True) + config["norm_topk_eps"])
    return w * config["routed_scaling_factor"]


def expert_layer(config, p, x, precision):
    f = config["moe_intermediate_size"]
    h = _act(rms_norm(x, p["norm"], config["rms_norm_eps"]), precision)
    w = route(config, p, h)
    if not config.get("train_router", True):
        w = jax.lax.stop_gradient(w)        # departure (6)
    # departure (1): only the experts held here, one after the other
    held, first = config["num_experts"], config.get("experts_offset", 0)
    if not held:
        return x

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
    return _act(x + y, precision)


def forward(config, params, ids, precision="highest", loads=None):
    """Logits [B, S, vocab_size] float32 of token ids [B, S].  (Each
    block is a ``jax.checkpoint``: under differentiation its activations
    are recomputed, which changes the memory and not the numbers.)
    ``loads``: a list that receives, an expert layer, how many of the
    tokens chose each of the ``router_width`` experts."""
    def layer_of(function, *first):
        return jax.checkpoint(
            lambda p, x: function(config, *first, p, x, precision))
    params = iter(params)
    x = _act(next(params)["weights"][ids], precision)
    for layer, kind in enumerate(config["layer_types"]):
        x = layer_of(attention, kind)(next(params), x)
        p = next(params)
        assert config["mlp_layer_types"][layer] == "sparse", layer
        if loads is not None:
            h = _act(rms_norm(x, p["norm"], config["rms_norm_eps"]),
                     precision)
            loads.append(route(config, p, h, chosen_only=True).sum(
                axis=(0, 1), dtype=jnp.int32))
        x = layer_of(expert_layer)(p, x)
    head = next(params)
    h = _act(rms_norm(x, head["norm"], config["rms_norm_eps"]), precision)
    return _mm("bsd,dv->bsv", h, head["weights"], precision)


def router_loads(config, params, ids, precision="highest"):
    """[expert layers, router_width] int32: the tokens of ``ids`` [B, S]
    that chose each routed expert, a sequence at a time."""
    params = jax.tree.map(jnp.asarray, params)

    def one(sequence):
        loads = []
        forward(config, params, sequence[None], precision, loads)
        return jnp.stack(loads)
    return jax.lax.map(one, ids).sum(axis=0)


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
    steps already made)``.  With ``bias_update_rate`` every step also
    moves each expert layer's ``router_bias`` by ``rate * (mean load -
    load) / mean load`` (``bias_update_rule`` ``proportional``; by
    ``rate * sign(mean load - load)`` under ``sign``; Wang et al.,
    arXiv:2408.15664, give both), the loads those of the step's own
    forward pass (the bias has no gradient, so AdamW leaves it where it
    is).  Returns (parameters, first moments, second moments)."""
    sol = config["solver"]
    lr, b1, b2 = sol["learning_rate"], sol["beta1"], sol["beta2"]
    eps, decay = sol["epsilon"], sol["weight_decay"]
    if state is None:
        state = (jax.tree.map(jnp.zeros_like, params),
                 jax.tree.map(jnp.zeros_like, params), 0)
    m, v, done = state
    rate = config.get("bias_update_rate", 0.0)
    by_sign = config.get("bias_update_rule", "sign") == "sign"
    for step in range(steps):
        t = jnp.asarray(done + step + 1, jnp.float32)
        _, grads = loss_and_grads(config, params, ids, labels, precision)
        loads = iter(router_loads(config, params, ids, precision)
                     if rate else ())
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)

        def update(w, m, v):
            change = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t))
                                            + eps)
            if w.ndim >= 2:
                change = change + decay * w
            return w - lr * change
        params = jax.tree.map(update, params, m, v)
        if rate:
            def balanced(p):
                load = next(loads).astype(jnp.float32)
                error = load.mean() - load
                error = jnp.sign(error) if by_sign else error / load.mean()
                return dict(p, router_bias=p["router_bias"] + rate * error)
            params = [balanced(p) if "router_bias" in p else p
                      for p in params]
    return params, m, v
