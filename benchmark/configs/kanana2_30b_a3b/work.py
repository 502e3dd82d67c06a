"""Operations, parameters and bytes of the ``kanana2_30b_a3b``
configuration, from the shapes in ``config.json`` alone (never from
XLA's ``cost_analysis``, which counts padding and recomputation).

A multiply-add is two operations.  The forward pass costs ``2 x MACs``,
the backward pass twice that (gradients with respect to the input and to
the weights); recomputed forward passes (the blocks are checkpointed) do
NOT count.  Causal attention counts the lower triangle.  The routed
experts count at the EXPECTED load of this chip's share: a token's
``num_experts_per_tok`` choices fall on a held expert with probability
``n_routed_experts / router_width`` each (6 x 16 / 128 = 0.75 a token).
Norms, rotary embedding, softmax, SiLU, the loss and AdamW are not
counted: the figure is the model FLOPs a utilisation is quoted against.

"Image" in the names the harness's readers call is one SEQUENCE of
``data.sequence_length`` tokens (the loader's sample).
"""


def attention_parameter_count(c):
    d, h = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (d * h * qk                                     # W_q
            + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])   # W_kva
            + c["kv_lora_rank"]                            # latent norm
            + c["kv_lora_rank"] * h * (c["qk_nope_head_dim"]
                                       + c["v_head_dim"])  # W_kvb
            + h * c["v_head_dim"] * d)                     # W_o


def expert_parameter_count(c):
    """One routed expert: gate, up, down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def dense_layer_parameter_count(c):
    return (attention_parameter_count(c) + 3 * c["hidden_size"]
            * c["intermediate_size"] + 2 * c["hidden_size"])


def expert_layer_parameter_count(c):
    d = c["hidden_size"]
    return (attention_parameter_count(c)
            + c["n_routed_experts"] * expert_parameter_count(c)
            + c["n_shared_experts"] * expert_parameter_count(c)
            + d * c["router_width"] + c["router_width"]    # router, b
            + 2 * d)


def parameter_count(c):
    """Parameters held on this chip: the depth run, the experts held,
    the vocabulary slice."""
    dense = c["first_k_dense_replace"]
    return (dense * dense_layer_parameter_count(c)
            + (c["n_layers"] - dense) * expert_layer_parameter_count(c)
            + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def forward_macs_per_token(c, seq=None):
    """{part: multiply-adds a token} of one forward pass at sequence
    length ``seq`` (default ``data.sequence_length``)."""
    seq = seq or c["data"]["sequence_length"]
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rot, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    rank, layers = c["kv_lora_rank"], c["n_layers"]
    moe_layers = layers - c["first_k_dense_replace"]
    f = c["moe_intermediate_size"]
    held = c["num_experts_per_tok"] * c["n_routed_experts"] \
        / c["router_width"]
    return {
        "projections": layers * (d * h * (nope + rot) + d * (rank + rot)
                                 + rank * h * (nope + vd) + h * vd * d),
        # a token attends to (seq + 1) / 2 positions on average
        "attention_core": layers * h * (nope + rot + vd) * (seq + 1) / 2,
        "dense_mlp": c["first_k_dense_replace"] * 3 * d
        * c["intermediate_size"],
        "router": moe_layers * d * c["router_width"],
        "routed_experts": moe_layers * held * 3 * d * f,
        "shared_experts": moe_layers * c["n_shared_experts"] * 3 * d * f,
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

def mla_flash_work(c, sequences, forward_only=0, seq=None, itemsize=2):
    """(operations, bytes) the three latent-attention kernels need for
    ``sequences`` trained sequences through every layer, forward and
    backward once each (the recomputed forward of a checkpointed block
    is NOT required work), and ``forward_only`` evaluated ones.
    Operations: the causal lower triangle of each product — forward 2
    (scores, values); dq pass 3 (scores, dO.V^T, dS.K); dk/dv pass 4
    (scores, P^T.dO, dO.V^T, dS^T.Q).  Bytes: every operand read once
    and every result written once by each kernel."""
    seq = seq or c["data"]["sequence_length"]
    h, layers = c["num_attention_heads"], c["n_layers"]
    nope, rot, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    pairs = seq * (seq + 1) / 2                 # (query, key) pairs a head
    qk, v = nope + rot, vd
    forward_ops = 2 * pairs * h * (qk + v)
    backward_ops = 2 * pairs * h * ((qk + v + qk)           # dq
                                    + (qk + v + v + qk))    # dk/dv
    q_bytes = seq * h * qk * itemsize           # q, or dq
    k_bytes = seq * (h * nope + rot) * itemsize  # k_nope and the one k_rope
    v_bytes = seq * h * v * itemsize            # v, out, dO, dv
    stats = seq * h * 4
    forward_bytes = (q_bytes + k_bytes + v_bytes) + (v_bytes + stats)
    backward_bytes = (
        (q_bytes + k_bytes + 2 * v_bytes + 2 * stats) + q_bytes      # dq
        + (q_bytes + k_bytes + 2 * v_bytes + 2 * stats)              # dk/dv
        + (seq * h * nope * itemsize + seq * h * rot * 4 + v_bytes))
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
    moe_layers = c["n_layers"] - c["first_k_dense_replace"]
    row_ops = 2 * 3 * d * f
    row_bytes = itemsize * ((d + 2 * f) + (f + d))
    weights = c["n_routed_experts"] * expert_parameter_count(c) * itemsize
    return ((3 * rows + forward_rows) * row_ops,
            (3 * rows + forward_rows) * row_bytes
            + (3 * steps + forward_steps) * moe_layers * weights)
