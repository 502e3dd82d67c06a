"""Operations, parameters and bytes of the ``lfm2_24b_a2b`` configuration,
from the shapes in ``config.json`` alone (never from XLA's
``cost_analysis``, which counts padding and recomputation).

A multiply-add is two operations.  The forward pass costs ``2 x MACs``,
the backward pass twice that (gradients with respect to the input and to
the weights); recomputed forward passes (the blocks are checkpointed) do
NOT count.  Causal attention counts the lower triangle.  The routed
experts count at the EXPECTED load of this chip's share: a token's
``num_experts_per_tok`` choices fall on a held expert with probability
``num_experts / router_width`` each (4 x 8 / 64 = 0.5 a token).  Norms,
rotary embedding, softmax, SiLU, the gates' products, the loss and AdamW
are not counted: the figure is the model FLOPs a utilisation is quoted
against.

"Image" in the names the harness's readers call is one SEQUENCE of
``data.sequence_length`` tokens (the loader's sample).
"""


def _head_dim(c):
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def _count(c, kind):
    return sum(1 for layer in c["layer_types"] if layer == kind)


def conv_parameter_count(c):
    """One short-convolution operator: norm, in_proj, taps, out_proj."""
    d = c["hidden_size"]
    return d + d * 3 * d + c["conv_L_cache"] * d + d * d


def attention_parameter_count(c):
    """One attention operator: norm, W_q, W_k, W_v, the two head norms,
    W_o."""
    d, k = c["hidden_size"], _head_dim(c)
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    return d + d * heads * k + 2 * d * kv * k + 2 * k + heads * k * d


def expert_parameter_count(c):
    """One routed expert: gate, up, down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def dense_mlp_parameter_count(c):
    return c["hidden_size"] + 3 * c["hidden_size"] * c["intermediate_size"]


def expert_block_parameter_count(c):
    """Norm, router and its bias, the experts HELD here."""
    d = c["hidden_size"]
    return (d + d * c["router_width"] + c["router_width"]
            + c["num_experts"] * expert_parameter_count(c))


def parameter_count(c):
    """Parameters held on this chip: the depth run, the experts held,
    the vocabulary slice (embedding and head apart)."""
    dense = c["num_dense_layers"]
    return (_count(c, "conv") * conv_parameter_count(c)
            + _count(c, "full_attention") * attention_parameter_count(c)
            + dense * dense_mlp_parameter_count(c)
            + (len(c["layer_types"]) - dense)
            * expert_block_parameter_count(c)
            + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def forward_macs_per_token(c, seq=None):
    """{part: multiply-adds a token} of one forward pass at sequence
    length ``seq`` (default ``data.sequence_length``)."""
    seq = seq or c["data"]["sequence_length"]
    d, k = c["hidden_size"], _head_dim(c)
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    convs, attns = _count(c, "conv"), _count(c, "full_attention")
    moe_layers = len(c["layer_types"]) - c["num_dense_layers"]
    held = c["num_experts_per_tok"] * c["num_experts"] / c["router_width"]
    return {
        "conv_projections": convs * (d * 3 * d + d * d),
        "conv_taps": convs * c["conv_L_cache"] * d,
        "attention_projections": attns * (2 * d * heads * k
                                          + 2 * d * kv * k),
        # a token attends to (seq + 1) / 2 positions on average: scores
        # and values, every QUERY head
        "attention_core": attns * heads * 2 * k * (seq + 1) / 2,
        "dense_mlp": c["num_dense_layers"] * 3 * d * c["intermediate_size"],
        "router": moe_layers * d * c["router_width"],
        "routed_experts": moe_layers * held * expert_parameter_count(c),
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

def gqa_flash_work(c, sequences, forward_only=0, seq=None, itemsize=2):
    """(operations, bytes) the three grouped-query flash kernels need
    for ``sequences`` trained sequences through every attention layer,
    forward and backward once each (the recomputed forward of a
    checkpointed block is NOT required work), and ``forward_only``
    evaluated ones.  Operations: the causal lower triangle of each
    product, every query head — forward 2 (scores, values); dq pass 3
    (scores, dO.V^T, dS.K); dk/dv pass 4 (scores, P^T.dO, dO.V^T,
    dS^T.Q).  Bytes: every operand read once and every result written
    once by each kernel, K, V and their gradients at the KEY-VALUE head
    count, the row statistics one float32 a query row."""
    seq = seq or c["data"]["sequence_length"]
    heads, kv, k = (c["num_attention_heads"], c["num_key_value_heads"],
                    _head_dim(c))
    layers = _count(c, "full_attention")
    pairs = seq * (seq + 1) / 2                 # (query, key) pairs a head
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
    moe_layers = len(c["layer_types"]) - c["num_dense_layers"]
    row_ops = 2 * 3 * d * f
    row_bytes = itemsize * ((d + 2 * f) + (f + d))
    weights = c["num_experts"] * expert_parameter_count(c) * itemsize
    return ((3 * rows + forward_rows) * row_ops,
            (3 * rows + forward_rows) * row_bytes
            + (3 * steps + forward_steps) * moe_layers * weights)
