"""Transformer blocks as Znicz forward units: a token embedding, a
latent-attention block, a grouped-query attention block, a gated
short-convolution block, a gated-MLP block, an expert block and a
normalised head over a vocabulary slice.

Each is a :class:`ForwardBase` with a ``MAPPING``, so a
``StandardWorkflow`` ``layers`` list builds a decoder out of them and the
fused / epoch-scan trainers chain their pure ``apply(params, x)`` like any
other layer's.  Pre-norm and residual live INSIDE a block (``x + f(norm(
x))``), so the chain stays a chain.  The blocks are driven by the keys a
public ``config.json`` uses (``qk_nope_head_dim``, ``kv_lora_rank``,
``n_routed_experts``, ``num_key_value_heads``, ``conv_L_cache``,
``sliding_window``, ``rope_parameters``, ``scoring_func``, ...) and by
the share of a deployment this chip holds (``experts_held``,
``experts_offset``), never by a model's name.

Arithmetic under ``--compute-dtype bfloat16``: matrix operands bfloat16,
sums float32; the router, every norm's statistics, the rotary angles and
the loss float32 (``FLOAT32_PARAMS`` names the tensors the trainer's
boundary cast leaves alone).  Weights are drawn ON THE DEVICE from the
unit's seed (``init_params``): a host draw of half a billion numbers and
its upload would be tens of seconds of set-up.

What a unit tells the trainer (``fused.py`` reads these attributes of any
forward unit): ``remat`` asks for ``jax.checkpoint`` around ``apply``
(the block's activations are recomputed in the backward pass);
``remat_saves`` lists the values, by their ``checkpoint_name``, that the
checkpoint keeps from the forward pass instead (the attention block: the
flash kernel's output and row statistics, so the backward pass reruns
the cheap projections around the kernel and not the kernel);
``apply_stats`` returns ``(y, stats)`` with counters the step's device
accumulator sums; ``token_loss`` (the head) folds the vocabulary
projection and the loss over blocks of tokens.
"""

import collections
import functools
import math

import numpy

from ..memory import Array
from .nn_units import ForwardBase, GradientDescentBase


def rms_norm(x, weight, eps):
    """``x / sqrt(mean(x^2) + eps) * weight``, statistics in float32,
    result in ``x``'s dtype."""
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    inv = jnp.reciprocal(jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                                  + eps))
    return (xf * inv * weight.astype(jnp.float32)).astype(x.dtype)


def yarn_band(d, theta, original_max, beta_fast, beta_slow):
    """(low, high): the pairs of a ``d``-wide head between which YaRN
    blends the published frequency into the stretched one.  A pair that
    turns ``r`` times over ``original_max`` positions has the index ``d
    ln(original_max / (2 pi r)) / (2 ln theta)``: pairs up to ``low``
    (``beta_fast`` turns and more) stay, pairs from ``high``
    (``beta_slow`` and fewer) stretch."""
    def pair(turns):
        return d * math.log(original_max / (2 * math.pi * turns)) \
            / (2 * math.log(theta))
    return (max(math.floor(pair(beta_fast)), 0),
            min(math.ceil(pair(beta_slow)), d - 1))


def _rope_angles(t, d, theta, scaling=None):
    """(cos, sin) [T, D/2] float32 of positions 0..T-1: pair ``i`` of a
    ``D``-wide head turns by ``position * theta ** (-2i / D)``.

    ``scaling``: a ``rope_parameters`` group of ``rope_type`` ``yarn``
    (Peng et al., arXiv:2309.00071): pair ``i`` turns at ``1 - ramp_i *
    (1 - 1 / factor)`` of its frequency, ``ramp`` rising from 0 at
    ``low`` to 1 at ``high`` (:func:`yarn_band`: ``factor`` times slower
    from there), and cos and sin are multiplied by
    ``attention_factor`` (``0.1 ln(factor) + 1`` where the group gives
    none), so that the scores carry its square.  At every length, not
    only past ``original_max_position_embeddings``.  ``factor`` 1 is the
    default's angles bit for bit."""
    import jax.numpy as jnp
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if scaling is not None:
        factor = float(scaling["factor"])
        low, high = yarn_band(
            d, theta, scaling["original_max_position_embeddings"],
            scaling.get("beta_fast", 32), scaling.get("beta_slow", 1))
        ramp = jnp.clip(
            (jnp.arange(d // 2, dtype=jnp.float32) - low)
            / (high - low if high > low else 0.001), 0.0, 1.0)
        # interp * ramp + extrap * (1 - ramp), interp = extrap / factor
        inv_freq = inv_freq + (inv_freq / factor - inv_freq) * ramp
        gain = scaling.get("attention_factor")
        if gain is None:
            gain = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    # the default type's program stays what it was: no product by one
    return (cos, sin) if scaling is None else (cos * gain, sin * gain)


def rope_interleaved(x, theta):
    """Rotary embedding over the last axis of ``x`` [..., T, D] with the
    pairs ``(2i, 2i+1)`` (``rope_interleave``), positions 0..T-1, angles
    and rotation in float32, result in ``x``'s dtype."""
    import jax.numpy as jnp
    t, d = x.shape[-2], x.shape[-1]
    cos, sin = _rope_angles(t, d, theta)
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def rope_half_split(x, theta, seq_axis=-2, scaling=None):
    """The same rotation with the pairs ``(i, i + D/2)`` (the
    ``rotate_half`` convention of the Llama lineage): the first half of a
    head holds the pairs' first members, the second half their second.
    ``seq_axis`` is the axis of the positions (-3 for [B, T, H, D]);
    ``scaling`` as :func:`_rope_angles` reads it."""
    import jax.numpy as jnp
    t, d = x.shape[seq_axis], x.shape[-1]
    cos, sin = (a.reshape((t,) + (1,) * (-seq_axis - 2) + (d // 2,))
                for a in _rope_angles(t, d, theta, scaling))
    xf = x.astype(jnp.float32)
    a, b = xf[..., :d // 2], xf[..., d // 2:]
    out = jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.astype(x.dtype)


def gated_mlp(h, gate, up, down):
    """``(silu(h gate) * (h up)) down`` with float32 sums, in ``h``'s
    dtype between the products."""
    import jax
    import jax.numpy as jnp
    g = jnp.dot(h, gate, preferred_element_type=jnp.float32)
    u = jnp.dot(h, up, preferred_element_type=jnp.float32)
    a = (jax.nn.silu(g) * u).astype(h.dtype)
    return jnp.dot(a, down, preferred_element_type=jnp.float32)


class BlockBase(ForwardBase):
    """A forward unit whose parameters are a dictionary of named tensors
    drawn on the device.  Subclasses give ``tensor_shapes()`` ->
    ``{name: (shape, kind)}`` with ``kind`` ``"matrix"`` (normal,
    ``weights_stddev``), ``"ones"`` (a norm's weight) or ``"bias"``
    (normal, ``bias_stddev``: an untrained buffer)."""

    hide_from_registry = True
    #: tensors that stay float32 under a bfloat16 compute dtype
    FLOAT32_PARAMS = ("norm",)
    #: ask the trainer for jax.checkpoint around apply
    remat = True
    #: the ``checkpoint_name``s whose values that checkpoint keeps for the
    #: backward pass (``save_only_these_names``); none: all is recomputed
    remat_saves = ()

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        self.weights_stddev = float(kwargs.get("weights_stddev", 0.02))
        self.bias_stddev = float(kwargs.get("bias_stddev", 0.01))
        self.seed = int(kwargs.get("seed", 0))
        self.rms_norm_eps = float(kwargs.get("rms_norm_eps", 1e-6))
        self.tensors = {}
        self.exports = []

    def tensor_shapes(self):
        raise NotImplementedError

    def init_params(self):
        import jax
        import jax.numpy as jnp
        shapes = self.tensor_shapes()
        stddev, bias_stddev = self.weights_stddev, self.bias_stddev

        def draw(key):
            out = {}
            for i, (name, (shape, kind)) in enumerate(sorted(
                    shapes.items())):
                if kind == "ones":
                    out[name] = jnp.ones(shape, jnp.float32)
                else:
                    scale = stddev if kind == "matrix" else bias_stddev
                    out[name] = scale * jax.random.normal(
                        jax.random.fold_in(key, i), shape, jnp.float32)
            return out
        # two 32-bit words: a seed may be wider than int32
        key = jnp.asarray([self.seed >> 32 & 0xFFFFFFFF,
                           self.seed & 0xFFFFFFFF], jnp.uint32)
        drawn = jax.jit(lambda k: draw(jax.random.wrap_key_data(
            k, impl="threefry2x32")))(key)
        self.set_params(drawn)
        self.exports = sorted(self.tensors)

    @property
    def params(self):
        return {name: a.devmem for name, a in self.tensors.items()}

    def set_params(self, params):
        for name, value in params.items():
            self.tensors.setdefault(name, Array()).devmem = value

    @property
    def host_params(self):
        return {name: a.map_read() for name, a in self.tensors.items()}

    def set_host_params(self, params):
        for name, value in params.items():
            self.tensors.setdefault(name, Array()).mem = numpy.asarray(
                value, numpy.float32)

    def output_shape_for(self, input_shape):
        return tuple(input_shape)

    def initialize(self, device=None, **kwargs):
        from ..verified import verify_contract
        verify_contract(self, ForwardBase)
        super(ForwardBase, self).initialize(device=device, **kwargs)
        if not self.tensors:
            self.init_params()
        shape = tuple(self.output_shape_for(self.input_shape))
        if not self.output or tuple(self.output.shape) != shape:
            # a shape for the next unit to size itself by, not a buffer:
            # [B, S, V] zeros would be a gigabyte of host memory
            self.output.reset(numpy.broadcast_to(
                numpy.zeros((), numpy.float32), shape))

    def _norm(self, x, weight):
        return rms_norm(x, weight, self.rms_norm_eps)


class TokenEmbedding(BlockBase):
    """Token ids [B, S] (any integer dtype) -> [B, S, hidden].  The table
    stays float32 (its gradient is a scatter-add over repeated ids, which
    bfloat16 sums would lose); the rows come out float32 and the trainer casts
    them to its compute dtype."""

    MAPPING = "token_embedding"
    FLOAT32_PARAMS = ("weights",)
    remat = False

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        self.vocab_size = int(kwargs["vocab_size"])
        self.hidden_size = int(kwargs["hidden_size"])

    def tensor_shapes(self):
        return {"weights": ((self.vocab_size, self.hidden_size), "matrix")}

    def output_shape_for(self, input_shape):
        return tuple(input_shape) + (self.hidden_size,)

    def apply(self, params, x):
        import jax.numpy as jnp
        return jnp.take(params["weights"], x, axis=0)


class LatentAttentionBlock(BlockBase):
    """``x + Attention(RMSNorm(x))`` with multi-head latent attention
    (DeepSeek-V2): uncompressed queries of ``qk_nope_head_dim +
    qk_rope_head_dim`` a head; keys and values from one compressed
    ``kv_lora_rank`` latent a token (normalised) plus ONE rotary key a
    token shared by every head; value heads of ``v_head_dim``; causal.
    No bias anywhere.  The core runs the ``mla_flash_*`` kernels
    (``flash_attention.py``) on a TPU and explicit scores elsewhere."""

    MAPPING = "latent_attention_block"
    FLOAT32_PARAMS = ("norm", "kv_norm")
    #: ``flash_attention.MLA_SAVED_NAMES``: the kernel's two results, 136
    #: MB a block at 2 x 8,192 tokens, where recomputing them reran the
    #: forward kernel, 13.6 ms a block a step (PERF.md section 6, PR 31)
    remat_saves = ("mla_flash_out", "mla_flash_lse")

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        self.hidden_size = int(kwargs["hidden_size"])
        self.heads = int(kwargs["num_attention_heads"])
        self.nope = int(kwargs["qk_nope_head_dim"])
        self.rope = int(kwargs["qk_rope_head_dim"])
        self.v_dim = int(kwargs["v_head_dim"])
        self.kv_rank = int(kwargs["kv_lora_rank"])
        self.rope_theta = float(kwargs.get("rope_theta", 10000.0))
        self.use_pallas = kwargs.get("use_pallas")

    def tensor_shapes(self):
        d, h = self.hidden_size, self.heads
        return {
            "norm": ((d,), "ones"),
            "wq": ((d, h * (self.nope + self.rope)), "matrix"),
            "wkva": ((d, self.kv_rank + self.rope), "matrix"),
            "kv_norm": ((self.kv_rank,), "ones"),
            "wkvb": ((self.kv_rank, h * (self.nope + self.v_dim)),
                     "matrix"),
            "wo": ((h * self.v_dim, d), "matrix"),
        }

    def _core(self, q_nope, q_rope, k_nope, k_rope, v):
        from .flash_attention import (mla_attention_reference,
                                      mla_flash_attention)
        from .nn_units import resolve_use_pallas
        if resolve_use_pallas(self.use_pallas, self.device, tpu_auto=True):
            return mla_flash_attention(q_nope, q_rope, k_nope, k_rope, v)
        return mla_attention_reference(q_nope, q_rope, k_nope, k_rope, v)

    def apply(self, params, x):
        import jax
        import jax.numpy as jnp
        f32 = jnp.float32
        d, h = self.hidden_size, self.heads
        hn = self._norm(x, params["norm"])
        with jax.named_scope("mla/q"):
            q = jnp.einsum("bsd,dhk->bhsk", hn, params["wq"].reshape(
                d, h, self.nope + self.rope),
                preferred_element_type=f32).astype(x.dtype)
        with jax.named_scope("mla/kv_down"):
            ckv = jnp.dot(hn, params["wkva"], preferred_element_type=f32)
            latent = self._norm(ckv[..., :self.kv_rank].astype(x.dtype),
                                params["kv_norm"])
            k_rope = ckv[..., self.kv_rank:]           # [B, S, rope], f32
        with jax.named_scope("mla/kv_up"):
            kv = jnp.einsum("bsr,rhk->bhsk", latent, params["wkvb"].reshape(
                self.kv_rank, h, self.nope + self.v_dim),
                preferred_element_type=f32).astype(x.dtype)
        with jax.named_scope("mla/rope"):
            q_rope = rope_interleaved(q[..., self.nope:], self.rope_theta)
            k_rope = rope_interleaved(k_rope, self.rope_theta).astype(
                x.dtype)
        with jax.named_scope("mla/core"):
            out = self._core(q[..., :self.nope], q_rope,
                             kv[..., :self.nope], k_rope,
                             kv[..., self.nope:])
        with jax.named_scope("mla/out"):
            y = jnp.einsum("bhsv,hvd->bsd", out, params["wo"].reshape(
                h, self.v_dim, d), preferred_element_type=f32)
        return (x.astype(f32) + y).astype(x.dtype)


class GQAAttentionBlock(BlockBase):
    """``x + Attention(RMSNorm(x))`` with grouped-query attention:
    ``num_attention_heads`` query heads read ``num_key_value_heads``
    key-value heads (query head ``h`` reads ``h // group``), queries and
    keys RMS-normalised over each head's ``head_dim`` with one learned
    weight each (float32 statistics), rotary embedding over the whole
    head in the half-split pairing, causal softmax with scale ``1 /
    sqrt(head_dim)``.  No bias anywhere.  The core runs the plain flash
    kernels (``flash_attention.py``: K and V stay at their own head
    count) on a TPU and explicit scores elsewhere.

    Two arguments make the kinds of layer one depth mixes
    (``layer_types``) out of the one unit.  ``sliding_window`` (absent:
    a full layer): position ``i`` sees the keys in ``(i -
    sliding_window, i]``, on the kernels' banded grids.
    ``rope_parameters``: the layer kind's own group of a
    ``config.json`` (``rope_theta``, ``rope_type`` ``default`` or
    ``yarn`` with its ``factor``, ``original_max_position_embeddings``,
    ``beta_fast``, ``beta_slow``, ``attention_factor``); a bare
    ``rope_theta`` is the default type's."""

    MAPPING = "gqa_attention_block"
    FLOAT32_PARAMS = ("norm", "q_norm", "k_norm")
    #: ``flash_attention.SAVED_NAMES``: the kernel's output and row
    #: statistics, 69 MB a block at 2 x 8,192 tokens and 32 heads of 64,
    #: so that the backward pass does not rerun the forward kernel
    remat_saves = ("flash_out", "flash_lse")

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        self.hidden_size = int(kwargs["hidden_size"])
        self.heads = int(kwargs["num_attention_heads"])
        self.kv_heads = int(kwargs.get("num_key_value_heads", self.heads))
        self.head_dim = int(kwargs.get("head_dim")
                            or self.hidden_size // self.heads)
        rope = dict(kwargs.get("rope_parameters") or {})
        self.rope_theta = float(rope.get(
            "rope_theta", kwargs.get("rope_theta", 10000.0)))
        self.rope_type = rope.get("rope_type", "default")
        if self.rope_type not in ("default", "yarn"):
            raise ValueError("no rotary angles of rope_type %r"
                             % (self.rope_type,))
        #: what ``_rope_angles`` reads of a scaled type; None: default
        self.rope_scaling = rope if self.rope_type == "yarn" else None
        window = kwargs.get("sliding_window")
        self.sliding_window = None if window is None else int(window)
        self.use_pallas = kwargs.get("use_pallas")
        if self.heads % self.kv_heads:
            raise ValueError("%d query heads cannot share %d key-value "
                             "heads" % (self.heads, self.kv_heads))

    @property
    def remat_note(self):
        """What tells this unit from its neighbour of the same class, for
        the step's ``step.remat`` record."""
        return "window=%s,rope=%s" % (self.sliding_window, self.rope_type)

    def tensor_shapes(self):
        d, k = self.hidden_size, self.head_dim
        return {
            "norm": ((d,), "ones"),
            "wq": ((d, self.heads * k), "matrix"),
            "wk": ((d, self.kv_heads * k), "matrix"),
            "wv": ((d, self.kv_heads * k), "matrix"),
            "q_norm": ((k,), "ones"),
            "k_norm": ((k,), "ones"),
            "wo": ((self.heads * k, d), "matrix"),
        }

    def _core(self, q, k, v):
        from ..parallel.ring import attention_reference
        from .flash_attention import flash_attention
        from .nn_units import resolve_use_pallas
        if resolve_use_pallas(self.use_pallas, self.device, tpu_auto=True):
            return flash_attention(q, k, v, causal=True,
                                   window=self.sliding_window)
        return attention_reference(q, k, v, causal=True,
                                   window=self.sliding_window)

    def apply(self, params, x):
        import jax
        import jax.numpy as jnp
        f32 = jnp.float32
        d, k = self.hidden_size, self.head_dim
        hn = self._norm(x, params["norm"])

        def heads(name, n):
            return jnp.einsum("bsd,dhk->bshk", hn, params[name].reshape(
                d, n, k), preferred_element_type=f32).astype(x.dtype)

        def turned(name, n, norm):
            return rope_half_split(
                self._norm(heads(name, n), params[norm]), self.rope_theta,
                seq_axis=-3, scaling=self.rope_scaling)
        with jax.named_scope("attn/qkv"):
            q = turned("wq", self.heads, "q_norm")
            key = turned("wk", self.kv_heads, "k_norm")
            v = heads("wv", self.kv_heads)
        with jax.named_scope("attn/core"):
            out = self._core(q, key, v)                # [B, S, H, k]
        with jax.named_scope("attn/out"):
            y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].reshape(
                self.heads, k, d), preferred_element_type=f32)
        return (x.astype(f32) + y).astype(x.dtype)


class ShortConvBlock(BlockBase):
    """``x + ((C * conv(B * x~)) W_out)`` with ``[B, C, x~] = split(
    RMSNorm(x) W_in)``: a gated short convolution (the LFM2 operator).
    ``conv`` is causal and depthwise over ``conv_L_cache`` taps, ``v[t] =
    sum_j w[j] * u[t - (L-1) + j]`` with ``u`` zero before the sequence's
    start, no bias, no activation function: the two gates are the only
    non-linearity.  The taps are ``L`` shifted multiply-adds summed in
    float32 (their gradient is shifted sums too, where a 2,048-group
    ``conv_general_dilated`` would make XLA derive a grouped
    convolution's).  ``conv`` is stored [L, d]: tap ``j`` of every
    channel is one row."""

    MAPPING = "short_conv_block"

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        self.hidden_size = int(kwargs["hidden_size"])
        self.taps = int(kwargs.get("conv_L_cache", 3))

    def tensor_shapes(self):
        d = self.hidden_size
        return {"norm": ((d,), "ones"),
                "in_proj": ((d, 3 * d), "matrix"),
                "conv": ((self.taps, d), "matrix"),
                "out_proj": ((d, d), "matrix")}

    def apply(self, params, x):
        import jax
        import jax.numpy as jnp
        f32 = jnp.float32
        t, taps = x.shape[1], self.taps
        hn = self._norm(x, params["norm"])
        with jax.named_scope("conv/in_proj"):
            bcx = jnp.dot(hn, params["in_proj"],
                          preferred_element_type=f32).astype(x.dtype)
            gate_b, gate_c, xt = jnp.split(bcx, 3, axis=-1)
        with jax.named_scope("conv/mix"):
            u = (gate_b.astype(f32) * xt.astype(f32)).astype(x.dtype)
            # u[t - s] with zeros before the start: s zeros in front,
            # the first T positions kept
            padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
            w = params["conv"].astype(x.dtype).astype(f32)
            v = sum(w[j] * padded[:, j:j + t].astype(f32)
                    for j in range(taps))
            g = (gate_c.astype(f32) * v).astype(x.dtype)
        with jax.named_scope("conv/out_proj"):
            y = jnp.dot(g, params["out_proj"], preferred_element_type=f32)
        return (x.astype(f32) + y).astype(x.dtype)


class GatedMLPBlock(BlockBase):
    """``x + (silu(h Wg) * h Wu) Wd`` with ``h = RMSNorm(x)``."""

    MAPPING = "gated_mlp_block"

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        self.hidden_size = int(kwargs["hidden_size"])
        self.intermediate_size = int(kwargs["intermediate_size"])

    def tensor_shapes(self):
        d, f = self.hidden_size, self.intermediate_size
        return {"norm": ((d,), "ones"), "gate": ((d, f), "matrix"),
                "up": ((d, f), "matrix"), "down": ((f, d), "matrix")}

    def apply(self, params, x):
        import jax.numpy as jnp
        hn = self._norm(x, params["norm"])
        y = gated_mlp(hn, params["gate"], params["up"], params["down"])
        return (x.astype(jnp.float32) + y).astype(x.dtype)


#: the expert block's row buffer holds this many times a chip's share of
#: evenly spread choices (the records: a taught router reads 1.0-1.14 x
#: its share over a window, a balanced one 0.96-1.07 x; PERF.md section
#: 6, PR 34).  Too small costs time, never rows.
BUFFER_SHARES = 2
#: the grouped product's largest row tile (``gemm.grouped_matmul``)
ROW_TILE = 512
#: a step's routing, made once in the forward pass: ``order`` [T * k,
#: padded to whole blocks], the choices (token * k + j) sorted by held
#: expert, absent experts' last; ``pos`` [T, k], its inverse; ``here``
#: [T, k], the choices on a held expert; ``sizes`` [held], rows an expert
_Plan = collections.namedtuple("_Plan", "order pos here sizes")


class ExpertBlock(BlockBase):
    """``x + sum_e w_e E_e(h) + Shared(h)``, ``h = RMSNorm(x)``: a
    float32 router over ALL ``n_routed_experts`` whose scores ``s`` are
    ``scoring_func`` of its logits (``sigmoid``, each expert alone, the
    default; ``softmax`` over all of them), the
    ``num_experts_per_tok`` largest ``s + b`` chosen (``b``: the
    ``noaux_tc`` correction bias, used for the choice only), weights
    ``routed_scaling_factor * s / (sum s + norm_topk_eps)`` over the
    chosen.  This chip
    holds ``experts_held`` experts from ``experts_offset``; tokens routed
    to them are sorted by expert and go through a grouped matrix product
    whose group sizes are the data's (``gemm.grouped_matmul``); what the
    absent experts would add is left out.

    No capacity, and no token is ever dropped.  The row buffer holds
    ``buffer_rows`` rows: ``BUFFER_SHARES`` times this chip's share of
    every token's every choice, and all of them where the chip holds
    every expert.  A step whose routed rows exceed the buffer runs the
    same routine over as many such buffers as hold rows, up to the full
    bound (counter ``moe_spilled``): slower, the same result.

    ``bias_update_rate`` > 0 turns on the balancing update the bias
    exists for (DeepSeek-V3, section 2.1.2): after every train step
    ``b_e += rate * sign(mean load - load_e)``, the load being the
    step's tokens that chose expert ``e`` among ALL routed experts
    (counter ``router_load``).  At 0, the default, ``b`` is a buffer
    nothing touches.  ``bias_update_rule`` ``proportional`` takes the
    error itself for its sign, ``b_e += rate * (mean load - load_e) /
    mean load`` (the other form Wang et al., arXiv:2408.15664, give the
    rule): a bias that stays bounded then holds every expert's load AVERAGED
    over the steps at the mean, whatever moves together, where the sign
    holds the median step there (a lump of tokens that flips between
    two experts leaves each at half the lump).

    ``train_router`` False leaves the routing weights out of the
    backward pass, so the router matrix gets no gradient: what a share
    of an expert-parallel layer (``experts_held`` < ``n_routed_experts``)
    needs, because a router's gradient is made of the outputs of ALL the
    experts a token chose and a share has its own alone: taught by those,
    it learns that the absent experts add nothing and moves every token
    onto the held ones."""

    MAPPING = "expert_block"
    FLOAT32_PARAMS = ("norm", "router", "router_bias")

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        self.hidden_size = int(kwargs["hidden_size"])
        self.width = int(kwargs["moe_intermediate_size"])
        self.n_experts = int(kwargs["n_routed_experts"])
        self.top_k = int(kwargs["num_experts_per_tok"])
        self.n_shared = int(kwargs.get("n_shared_experts", 0))
        self.scaling = float(kwargs.get("routed_scaling_factor", 1.0))
        self.scoring_func = kwargs.get("scoring_func", "sigmoid")
        if self.scoring_func not in ("sigmoid", "softmax"):
            raise ValueError("no router scores by %r"
                             % (self.scoring_func,))
        self.norm_topk = bool(kwargs.get("norm_topk_prob", True))
        #: added to the sum the chosen scores are normalised by: 1e-20
        #: in the DeepSeek-V3 family's code, 1e-6 in LFM2's
        self.norm_topk_eps = float(kwargs.get("norm_topk_eps", 1e-20))
        self.bias_update_rate = float(kwargs.get("bias_update_rate", 0.0))
        self.bias_update_rule = kwargs.get("bias_update_rule", "sign")
        if self.bias_update_rule not in ("sign", "proportional"):
            raise ValueError("no balancing update by %r"
                             % (self.bias_update_rule,))
        self.train_router = bool(kwargs.get("train_router", True))
        self.held = int(kwargs.get("experts_held", self.n_experts))
        self.offset = int(kwargs.get("experts_offset", 0))
        if not 0 <= self.offset <= self.n_experts - self.held:
            raise ValueError("experts [%d, %d) are not among the %d routed"
                             % (self.offset, self.offset + self.held,
                                self.n_experts))

    def tensor_shapes(self):
        d, f, e = self.hidden_size, self.width, self.held
        shapes = {"norm": ((d,), "ones"),
                  "router": ((d, self.n_experts), "matrix"),
                  "router_bias": ((self.n_experts,), "bias"),
                  # gate and up side by side: one grouped product
                  "experts_gate_up": ((e, d, 2 * f), "matrix"),
                  "experts_down": ((e, f, d), "matrix")}
        if self.n_shared:
            fs = self.n_shared * f
            shapes.update({"shared_gate": ((d, fs), "matrix"),
                           "shared_up": ((d, fs), "matrix"),
                           "shared_down": ((fs, d), "matrix")})
        return shapes

    def stats_shapes(self):
        """The int32 counters ``apply_stats`` returns, by name."""
        shapes = {"expert_tokens": (self.held,), "moe_rows": (),
                  "moe_routed": (), "moe_spilled": ()}
        if self.bias_update_rate:
            shapes["router_load"] = (self.n_experts,)
        return shapes

    def route(self, params, h):
        """(expert ids [T, k], weights [T, k] float32) of tokens ``h``
        [T, d]."""
        import jax
        import jax.numpy as jnp
        score = jax.nn.sigmoid if self.scoring_func == "sigmoid" \
            else jax.nn.softmax         # over the last axis: all experts
        scores = score(jnp.dot(
            h.astype(jnp.float32), params["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, chosen = jax.lax.top_k(
            scores + params["router_bias"].astype(jnp.float32), self.top_k)
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
        if self.norm_topk:
            weights = weights / (weights.sum(axis=-1, keepdims=True)
                                 + self.norm_topk_eps)
        if not self.train_router:
            weights = jax.lax.stop_gradient(weights)
        return chosen, weights * self.scaling

    def buffer_rows(self, tokens):
        """The row buffer's length for ``tokens`` tokens: ``BUFFER_SHARES``
        times this chip's share of evenly spread choices, in whole row
        tiles of the grouped product, and never more than every token's
        every choice (what a block that holds all its experts gets)."""
        full = tokens * self.top_k
        share = BUFFER_SHARES * full * self.held
        tiles = -(-share // (self.n_experts * ROW_TILE))
        return min(full, tiles * ROW_TILE)

    def _experts(self, rows, plan, block, hn, weights, gate_up, down):
        """What the sorted rows ``[block * rows, (block + 1) * rows)``
        add to ``sum_e w_e E_e(hn)`` [T, d] float32: dispatch, the two
        grouped products over the part of each group inside the block,
        combine.  Rows past the last group are never written by the
        grouped product, in either pass; nothing reads them
        (``_combine`` and ``_dispatch``'s backward take the held
        choices alone)."""
        import jax
        import jax.numpy as jnp
        from . import gemm
        lo = block * rows
        src = jax.lax.dynamic_slice_in_dim(plan.order, lo, rows)
        ends = jnp.cumsum(plan.sizes)
        sizes = jnp.clip(ends, lo, lo + rows) \
            - jnp.clip(ends - plan.sizes, lo, lo + rows)
        pos = plan.pos - lo
        here = plan.here & (pos >= 0) & (pos < rows)
        with jax.named_scope("moe/dispatch"):
            buf = _dispatch(hn, src, pos, here)
        with jax.named_scope("moe/experts"):
            gu = gemm.grouped_matmul(buf, gate_up, sizes)
            a = (jax.nn.silu(gu[:, :self.width].astype(jnp.float32))
                 * gu[:, self.width:].astype(jnp.float32)).astype(hn.dtype)
            out = gemm.grouped_matmul(a, down, sizes)
        with jax.named_scope("moe/combine"):
            return _combine(out, weights, src, pos, here)

    def _routed(self, plan, hn, weights, gate_up, down):
        """``sum_e w_e E_e(hn)`` [T, d] float32 over the held experts:
        ``_experts`` over as many blocks of ``buffer_rows`` sorted rows
        as hold rows, one in a step that does not spill.  A loop whose
        trip count is the data's, so the program holds ONE instance of
        the routine (a ``lax.cond`` between a compact and a full-bound
        instance lowers every kernel twice: 15 % of a cell's set-up,
        PERF.md section 6, PR 34); such a loop has no derivative, so
        both passes are written out under one ``custom_vjp``, and the
        backward pass recomputes each block as the unit's checkpoint
        would.  In a step that spills, sums over a token's choices and
        over an expert's rows are sums of per-block sums, each rounded
        to its tensor's dtype."""
        import jax
        import jax.numpy as jnp
        rows = self.buffer_rows(hn.shape[0])

        def over_blocks(fn, zeros, plan):
            if rows == plan.pos.size:
                return fn(0)
            return jax.lax.fori_loop(
                0, -(-plan.sizes.sum() // rows),
                lambda i, acc: jax.tree.map(jnp.add, acc, fn(i)), zeros)

        @jax.custom_vjp
        def routed(plan, *tensors):
            return over_blocks(
                lambda i: self._experts(rows, plan, i, *tensors),
                jnp.zeros(tensors[0].shape, jnp.float32), plan)

        def fwd(*args):
            return routed(*args), args

        def bwd(args, g):
            plan, *tensors = args

            def experts(block, hn, weights, gate_up, down):
                if not self.train_router:
                    # or the weights' gradient is computed in every
                    # block for ``route`` to throw away
                    weights = jax.lax.stop_gradient(weights)
                return self._experts(rows, plan, block, hn, weights,
                                     gate_up, down)
            grads = over_blocks(
                lambda i: jax.vjp(functools.partial(experts, i),
                                  *tensors)[1](g),
                tuple(jnp.zeros_like(t) for t in tensors), plan)
            return (None, *grads)

        routed.defvjp(fwd, bwd)
        return routed(plan, hn, weights, gate_up, down)

    def apply_stats(self, params, x):
        import jax
        import jax.numpy as jnp
        b, s, d = x.shape
        k, held = self.top_k, self.held
        rows = self.buffer_rows(b * s)
        hn = self._norm(x, params["norm"]).reshape(b * s, d)
        with jax.named_scope("moe/router"):
            chosen, weights = self.route(params, hn)
        with jax.named_scope("moe/plan"):
            local = chosen - self.offset                       # [T, k]
            here = (local >= 0) & (local < held)
            # rows of absent experts sort behind every group and are
            # never multiplied
            order = jnp.argsort(jnp.where(here, local, held).reshape(-1),
                                stable=True)
            sizes = jnp.sum(
                local.reshape(-1, 1) == jnp.arange(held)[None, :], axis=0,
                dtype=jnp.int32)
            # whole blocks to slice; nothing points at the padding
            plan = _Plan(jnp.pad(order, (0, -order.size % rows)),
                         jnp.argsort(order).reshape(b * s, k), here, sizes)
        y = self._routed(plan, hn, weights, params["experts_gate_up"],
                         params["experts_down"])
        if self.n_shared:
            with jax.named_scope("moe/shared"):
                y = y + gated_mlp(hn, params["shared_gate"],
                                  params["shared_up"],
                                  params["shared_down"])
        y = (x.astype(jnp.float32) + y.reshape(b, s, d)).astype(x.dtype)
        # rows routed here, counted from the router's choice, and rows
        # the grouped product was told to compute: equal iff dropless
        stats = {"expert_tokens": sizes,
                 "moe_rows": sizes.sum(),
                 "moe_routed": here.sum(dtype=jnp.int32),
                 "moe_spilled": (sizes.sum() > rows).astype(jnp.int32)}
        if self.bias_update_rate:
            stats["router_load"] = jnp.sum(
                chosen.reshape(-1, 1) == jnp.arange(self.n_experts)[None, :],
                axis=0, dtype=jnp.int32)
        return y, stats

    def update_buffers(self, params, stats):
        """{buffer: its value after a train step whose counters were
        ``stats``}: the trainer stores these and leaves the named
        tensors out of the solver's update."""
        import jax.numpy as jnp
        if not self.bias_update_rate:
            return {}
        load = stats["router_load"].astype(jnp.float32)
        error = load.mean() - load
        error = jnp.sign(error) if self.bias_update_rule == "sign" \
            else error / load.mean()
        return {"router_bias": params["router_bias"]
                + self.bias_update_rate * error}

    def apply(self, params, x):
        return self.apply_stats(params, x)[0]


def _spread(tok, src, k):
    """Tokens to sorted rows: ``rows[r] = tok[src[r] // k]`` for the
    ``len(src)`` first rows of the sort, ``src[r]`` being the choice
    (token * k + j) that sorted row ``r`` serves."""
    import jax.numpy as jnp
    return jnp.take(tok, src // k, axis=0, mode="clip")


def _gather_sum(buf, pos, here, w=None):
    """Sorted rows to tokens: ``y[t] = sum_j w[t, j] * buf[pos[t, j]]``
    in float32 over the choices that are ``here``; the others read
    nothing.  A gather of T * k rows and a sum over k, no scatter-add,
    which a TPU serialises.  ``where``, not times zero: rows past the
    last group are not written by the grouped product.  The rows are
    read choice by choice and summed slab by slab: a token's k rows
    side by side ([T, k, d]) would be tiles of 8 sublanes holding k,
    which the v5e lays out again at the cost of a second gather."""
    import jax.numpy as jnp
    t, k = pos.shape
    rows = jnp.take(buf, pos.T.reshape(-1), axis=0, mode="clip")
    y = 0.0
    for j in range(k):
        slab = jnp.where(here[:, j, None],
                         rows[j * t:(j + 1) * t].astype(jnp.float32), 0.0)
        y = y + (slab if w is None else slab * w[:, j, None])
    return y


def _dispatch(tok, src, pos, here):
    """``_spread(tok, src)`` whose backward pass is ``_gather_sum`` over
    the sort the forward pass made (``pos``, the inverse of ``src``):
    the cotangent of a token is the float32 sum of its held rows'."""
    import jax

    @jax.custom_vjp
    def dispatch(tok, src, pos, here):
        return _spread(tok, src, pos.shape[1])

    def fwd(tok, src, pos, here):
        return dispatch(tok, src, pos, here), (pos, here)

    def bwd(res, g):
        return _gather_sum(g, *res).astype(g.dtype), None, None, None

    dispatch.defvjp(fwd, bwd)
    return dispatch(tok, src, pos, here)


def _combine(buf, w, src, pos, here):
    """``_gather_sum(buf, pos, here, w)`` whose backward pass is
    ``_spread``: a row's cotangent is its weight times its token's, and
    a weight's the product of its row with its token's cotangent, taken
    over the sorted rows and unsorted as scalars; no [T, k, d]."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def combine(buf, w, src, pos, here):
        return _gather_sum(buf, pos, here, w)

    def fwd(buf, w, src, pos, here):
        return combine(buf, w, src, pos, here), (buf, w, src, pos, here)

    def bwd(res, g):
        buf, w, src, pos, here = res
        g_rows = _spread(g, src, pos.shape[1]).astype(jnp.float32)
        g_buf = g_rows * jnp.take(w.reshape(-1), src,
                                  mode="clip")[:, None]
        dots = jnp.sum(buf.astype(jnp.float32) * g_rows, axis=-1)
        g_w = jnp.where(here, jnp.take(dots, pos, mode="clip"), 0.0)
        return (g_buf.astype(buf.dtype), g_w.astype(w.dtype), None, None,
                None)

    combine.defvjp(fwd, bwd)
    return combine(buf, w, src, pos, here)


class NormHead(BlockBase):
    """Final RMSNorm and the untied head over this chip's slice of the
    vocabulary: ``apply`` gives float32 logits [B, S, V];
    ``token_loss`` gives the summed next-token cross-entropy and the
    count of wrong tokens with the projection taken over blocks of
    tokens, so the [tokens, V] float32 logits never exist whole."""

    MAPPING = "lm_head"
    remat = False

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        self.hidden_size = int(kwargs["hidden_size"])
        self.vocab_size = int(kwargs["vocab_size"])
        self.loss_block = int(kwargs.get("loss_block_tokens", 2048))

    def tensor_shapes(self):
        return {"norm": ((self.hidden_size,), "ones"),
                "weights": ((self.hidden_size, self.vocab_size), "matrix")}

    def output_shape_for(self, input_shape):
        return tuple(input_shape[:-1]) + (self.vocab_size,)

    def apply(self, params, x):
        import jax.numpy as jnp
        return jnp.dot(self._norm(x, params["norm"]), params["weights"],
                       preferred_element_type=jnp.float32)

    def token_loss(self, params, x, labels, mask):
        """(summed cross-entropy over unmasked tokens, wrong tokens,
        predicted ids [B, S]) of hidden states ``x`` [B, S, d] against
        ``labels`` [B, S]; ``mask`` [B] weighs whole sequences."""
        import jax
        import jax.numpy as jnp
        b, s, d = x.shape
        n = b * s
        block = math.gcd(n, self.loss_block)
        xs = x.reshape(n // block, block, d)
        ys = labels.reshape(n // block, block)
        ms = jnp.broadcast_to(mask[:, None], (b, s)).reshape(
            n // block, block)

        @jax.checkpoint
        def one(args):
            xb, yb, mb = args
            logits = self.apply(params, xb)            # [block, V] f32
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
            pred = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            wrong = ((pred != yb) & (mb > 0)).sum(dtype=jnp.int32)
            return ((lse - picked) * mb).sum(), wrong, pred
        with jax.named_scope("head/loss"):
            losses, wrongs, preds = jax.lax.map(one, (xs, ys, ms))
        return losses.sum(), wrongs.sum(), preds.reshape(b, s)


class GDBlock(GradientDescentBase):
    """Backward of a block through the VJP of its pure ``apply`` (the
    chain rule the fused trainer differentiates); the solver and its
    hyperparameters live here as for every layer."""

    hide_from_registry = True

    def backward(self, params, x, y, err_output, n_valid=None):
        if n_valid is None:
            n_valid = x.shape[0]
        return self.backward_via_vjp(params, x, err_output, n_valid)


class GDTokenEmbedding(GDBlock):
    MAPPING = "token_embedding"


class GDLatentAttentionBlock(GDBlock):
    MAPPING = "latent_attention_block"


class GDGQAAttentionBlock(GDBlock):
    MAPPING = "gqa_attention_block"


class GDShortConvBlock(GDBlock):
    MAPPING = "short_conv_block"


class GDGatedMLPBlock(GDBlock):
    MAPPING = "gated_mlp_block"


class GDExpertBlock(GDBlock):
    MAPPING = "expert_block"


class GDNormHead(GDBlock):
    MAPPING = "lm_head"
