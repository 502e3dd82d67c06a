"""Operations, parameters and bytes of the ``mellum2_12b_a2p5b``
configuration, from the shapes in ``config.json`` alone (never from XLA's
``cost_analysis``, which counts padding and recomputation).

A multiply-add is two operations.  The forward pass costs ``2 x MACs``,
the backward pass twice that (gradients with respect to the input and to
the weights); recomputed forward passes (the blocks are checkpointed) do
NOT count.  Causal attention counts the lower triangle, and inside a
sliding window only the band: ``T W - W (W - 1) / 2`` (query, key) pairs
a head a sequence of ``T >= W`` positions.  The routed experts count at
the EXPECTED load of this chip's share: a token's
``num_experts_per_tok`` choices fall on a held expert with probability
``num_experts / router_width`` each (8 x 16 / 64 = 2 a token).  Norms,
rotary embedding, softmax, SiLU, the gates' products, the loss and AdamW
are not counted: the figure is the model FLOPs a utilisation is quoted
against.

"Image" in the names the harness's readers call is one SEQUENCE of
``data.sequence_length`` tokens (the loader's sample).
"""


def _count(c, kind):
    """Layers of attention kind ``kind``; a ``sliding_attention`` layer
    with the windows switched off counts as a full one."""
    windows = c.get("use_sliding_window", True)
    return sum(1 for layer in c["layer_types"]
               if (layer == "sliding_attention" and windows)
               == (kind == "sliding_attention"))


def attended_pairs(seq, window=None):
    """(query, key) pairs of one head over one sequence: the causal lower
    triangle, or its band of ``window`` keys a query."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return seq * window - window * (window - 1) // 2


def attention_parameter_count(c):
    """One attention block: norm, W_q, W_k, W_v, the two head norms,
    W_o."""
    d, k = c["hidden_size"], c["head_dim"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    return d + d * heads * k + 2 * d * kv * k + 2 * k + heads * k * d


def expert_parameter_count(c):
    """One routed expert: gate, up, down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def expert_block_parameter_count(c):
    """Norm, router and the bias the block holds beside it, the experts
    HELD here."""
    d = c["hidden_size"]
    return (d + d * c["router_width"] + c["router_width"]
            + c["num_experts"] * expert_parameter_count(c))


def parameter_count(c):
    """Parameters held on this chip: the depth run, the experts held,
    the vocabulary slice (embedding and head apart)."""
    return (len(c["layer_types"]) * (attention_parameter_count(c)
                                     + expert_block_parameter_count(c))
            + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def forward_macs_per_token(c, seq=None):
    """{part: multiply-adds a token} of one forward pass at sequence
    length ``seq`` (default ``data.sequence_length``)."""
    seq = seq or c["data"]["sequence_length"]
    d, k = c["hidden_size"], c["head_dim"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    depth = len(c["layer_types"])           # every one with experts
    held = c["num_experts_per_tok"] * c["num_experts"] / c["router_width"]

    def core(kind, window):
        # scores and values, every QUERY head, the pairs a token on
        # average
        return _count(c, kind) * heads * 2 * k \
            * attended_pairs(seq, window) / seq
    return {
        "attention_projections": depth * (2 * d * heads * k
                                          + 2 * d * kv * k),
        "attention_core_full": core("full_attention", None),
        "attention_core_window": core("sliding_attention",
                                      c["sliding_window"]),
        "router": depth * d * c["router_width"],
        "routed_experts": depth * held * expert_parameter_count(c),
        "head": d * c["vocab_size"],
    }


def train_flops_per_token(c, seq=None):
    return 3 * 2 * sum(forward_macs_per_token(c, seq).values())


def train_flops_per_image(c):
    """Forward plus backward of one sequence, no recomputation."""
    return train_flops_per_token(c) * c["data"]["sequence_length"]


def forward_flops_per_image(c):
    return 2 * sum(forward_macs_per_token(c).values()) \
        * c["data"]["sequence_length"]


def dataset_bytes(c):
    data = c["data"]
    return (data["n_train"] + data["n_valid"]) * data["sequence_length"] \
        * 4 * 2                                # ids and labels, int32


# -- the kernels ---------------------------------------------------------------

def _flash_work(c, layers, pairs, sequences, forward_only, seq, itemsize):
    """(operations, bytes) the three flash kernels need for ``sequences``
    trained sequences through ``layers`` attention layers of ``pairs``
    (query, key) pairs a head, forward and backward once each (the
    recomputed forward of a checkpointed block is NOT required work),
    and ``forward_only`` evaluated ones.  Operations: every query head —
    forward 2 products a pair (scores, values); dq pass 3 (scores,
    dO.V^T, dS.K); dk/dv pass 4 (scores, P^T.dO, dO.V^T, dS^T.Q).
    Bytes: every operand read once and every result written once by
    each kernel (a band reads each K and V block once too: what a
    kernel reads again because its grid revisits a block is not
    required), K, V and their gradients at the KEY-VALUE head count, the
    row statistics one float32 a query row."""
    heads, kv, k = (c["num_attention_heads"], c["num_key_value_heads"],
                    c["head_dim"])
    forward_ops = 2 * pairs * heads * 2 * k
    backward_ops = 2 * pairs * heads * (3 * k + 4 * k)
    q_bytes = seq * heads * k * itemsize        # q, out, dO or dq
    kv_bytes = seq * kv * k * itemsize          # k, v, dk or dv
    stats = seq * heads * 4                     # lse or delta
    forward_bytes = (q_bytes + 2 * kv_bytes) + (q_bytes + stats)
    backward_bytes = (
        (2 * q_bytes + 2 * kv_bytes + 2 * stats) + q_bytes          # dq
        + (2 * q_bytes + 2 * kv_bytes + 2 * stats) + 2 * kv_bytes)  # dk/dv
    return (layers * ((sequences + forward_only) * forward_ops
                      + sequences * backward_ops),
            layers * ((sequences + forward_only) * forward_bytes
                      + sequences * backward_bytes))


def gqa_flash_work(c, sequences, forward_only=0, seq=None, itemsize=2):
    """The FULL layers' kernels (``gqa_flash_fwd`` / ``_dq`` / ``_dkv``):
    the causal lower triangle."""
    seq = seq or c["data"]["sequence_length"]
    return _flash_work(c, _count(c, "full_attention"), attended_pairs(seq),
                       sequences, forward_only, seq, itemsize)


def window_flash_work(c, sequences, forward_only=0, seq=None, itemsize=2):
    """The WINDOW layers' kernels (``gqa_window_flash_fwd`` / ``_dq`` /
    ``_dkv``): the band of ``sliding_window`` keys a query."""
    seq = seq or c["data"]["sequence_length"]
    return _flash_work(
        c, _count(c, "sliding_attention"),
        attended_pairs(seq, c["sliding_window"]), sequences, forward_only,
        seq, itemsize)


def grouped_matmul_work(c, rows, steps, forward_rows=0, forward_steps=0,
                        itemsize=2):
    """(operations, bytes) of the expert layers' grouped products for
    ``rows`` counted token-rows of ``steps`` train steps (the ``moe_rows``
    counter: a token counts once for each held expert it was routed to,
    summed over the expert layers), forward and backward once each, and
    ``forward_rows`` of ``forward_steps`` evaluation steps.  Operations:
    3 d f multiply-adds a row forward (gate and up as one product,
    down), twice that backward.  Bytes: the rows in and out of each
    product; the held experts' weights read once a layer and pass (a
    train step: forward, backward, and their gradient written)."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    row_ops = 2 * 3 * d * f
    row_bytes = itemsize * ((d + 2 * f) + (f + d))
    weights = c["num_experts"] * expert_parameter_count(c) * itemsize
    return ((3 * rows + forward_rows) * row_ops,
            (3 * rows + forward_rows) * row_bytes
            + (3 * steps + forward_steps) * len(c["layer_types"])
            * weights)
