"""Operations an AFMoE train step REQUIRES, from its static shapes and the
COUNTED rows of its expert layers (keys are those of a configuration file).
Recomputation is never counted: the backward pass is twice the forward pass,
whatever a kernel or a checkpoint redoes.  ``num_experts`` is the experts
HELD; a routed expert costs what its counted rows cost, whoever holds it."""


def _dims(conf):
    h, d = conf["hidden_size"], conf["head_dim"]
    return h, conf["num_attention_heads"] * d, conf["num_key_value_heads"] * d


def attention_params(conf):
    """wq, wg (the output gate), wo and wk, wv of one layer."""
    h, q, kv = _dims(conf)
    return 3 * h * q + 2 * h * kv


def expert_params(conf):
    """One SwiGLU of the expert width: a routed expert, or the shared one."""
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def dense_matmul_params(conf):
    """Matrix-multiplication parameters EVERY token meets: attention incl.
    the gate in every layer, the dense MLP or the shared expert and the
    router (at its published width), and the head over the vocabulary held.
    The embedding is a lookup; the norms are not matmuls."""
    h = conf["hidden_size"]
    n_dense = conf["num_dense_layers"]
    n_moe = conf["num_hidden_layers"] - n_dense
    router = h * conf["published"]["num_experts"]
    return (conf["num_hidden_layers"] * attention_params(conf)
            + n_dense * 3 * h * conf["intermediate_size"]
            + n_moe * (expert_params(conf) + router)
            + h * conf["vocab_size"])


def key_pairs(conf, kind, seq):
    """(query, key) pairs of one head over one sequence: all keys up to the
    query on a full layer, the last ``sliding_window`` of them on a windowed
    one."""
    w = conf["sliding_window"]
    if kind == "full_attention" or seq <= w:
        return seq * (seq + 1) / 2.0
    return seq * w - w * (w - 1) / 2.0


def attention_flops_per_seq(conf, seq):
    """QK^T and PV, forward (2 matmuls) and backward (4), every layer: 3 x
    2 matmuls x 2 FLOP x pairs x head_dim x heads."""
    _, q, _ = _dims(conf)
    return sum(12.0 * key_pairs(conf, kind, seq) * q
               for kind in conf["layer_types"])


def moe_gmm_flops_per_row(conf):
    """The grouped products of one routed (token, expert) row, forward and
    backward: 6 x the expert's parameters."""
    return 6.0 * expert_params(conf)


def train_flops_per_token(conf, seq, routed_rows_per_token):
    """6 x the parameters every token meets + 6 x an expert's parameters x
    the rows COUNTED per token (summed over the expert layers) + attention
    over the band each layer really has."""
    return (6.0 * dense_matmul_params(conf)
            + moe_gmm_flops_per_row(conf) * routed_rows_per_token
            + attention_flops_per_seq(conf, seq) / seq)


def flash_attention_flops_per_step(conf, batch, seq):
    return attention_flops_per_seq(conf, seq) * batch
