"""Operations a step REQUIRES, from its static shapes (model keys are those
of a configuration file's ``model`` group).  Recomputation is never counted:
the backward pass is twice the forward pass, whatever a kernel redoes."""


def matmul_params(model):
    """Parameters that take part in a matrix multiplication for every token:
    the blocks' projections and MLP plus the output head (the embedding is a
    lookup; the norms are not matmuls)."""
    h, i = model["hidden_size"], model["intermediate_size"]
    d = h // model["num_attention_heads"]
    kv = model["num_key_value_heads"] * d
    block = 2 * h * h + 2 * h * kv + 3 * h * i
    return model["num_hidden_layers"] * block + h * model["vocab_size"]


def causal_attention_flops_fwd(model, seq):
    """QK^T and PV of ONE layer over ONE sequence of ``seq`` tokens, causal
    (half the square): 2 matmuls x 2 FLOP x seq^2/2 x head_dim x heads."""
    h = model["hidden_size"]
    return 2.0 * seq * seq * h          # head_dim x heads == hidden_size


def train_flops_per_token(model, seq):
    """6 x matmul parameters + causal attention forward and backward."""
    attn = 3.0 * causal_attention_flops_fwd(model, seq) / seq
    return 6.0 * matmul_params(model) + model["num_hidden_layers"] * attn


def flash_attention_flops_per_step(model, batch, seq):
    """What the attention kernels of one train step must compute: forward
    (2 matmuls) and backward (4: dV, dP, dQ, dK) of every layer and
    sequence.  The backward's recomputation of QK^T is not counted."""
    return (3.0 * causal_attention_flops_fwd(model, seq) * batch
            * model["num_hidden_layers"])
