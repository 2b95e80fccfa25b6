"""Operations and bytes a Nemotron-H serving step REQUIRES, from the
configuration's static shapes and the program's counters (routed rows,
touched experts, live slot-steps): the same work whatever implements it.
Model keys are those of a configuration file.  Nothing recomputed, padded or
masked away is counted: a routed expert costs the rows it was GIVEN, an
expert's weights are read where at least one row reached it, a state is
read and written for a slot that is live."""


def _kinds(model):
    p = model["hybrid_override_pattern"]
    return p.count("M"), p.count("*"), p.count("E")


def _mamba_dims(model):
    heads, p = model["mamba_num_heads"], model["mamba_head_dim"]
    return heads, p, model["n_groups"], model["ssm_state_size"]


def dense_matmul_params(model):
    """Parameters that multiply EVERY token: the Mamba layers' two
    projections, the attention layers' four, and of a LatentMoE layer the
    router, the two latent projections and the shared expert.  The routed
    experts (by their rows) and the head (by the tokens that need logits)
    are counted apart."""
    n_m, n_a, n_e = _kinds(model)
    h = model["hidden_size"]
    heads, p, groups, n = _mamba_dims(model)
    d_in = heads * p
    mamba = h * (2 * d_in + 2 * groups * n + heads) + d_in * h
    q = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    attn = 2 * h * q + 2 * h * kv
    lat = model["moe_latent_size"]
    moe = h * model["published"]["n_routed_experts"] + 2 * h * lat \
        + 2 * h * model["moe_shared_expert_intermediate_size"]
    return n_m * mamba + n_a * attn + n_e * moe


def expert_params(model):
    """One routed expert: two matrices latent x intermediate."""
    return 2 * model["moe_latent_size"] * model["moe_intermediate_size"]


def expert_weight_bytes(model, itemsize=2):
    return expert_params(model) * itemsize


def head_params(model):
    return model["hidden_size"] * model["vocab_size"]


def ssd_scan_flops_per_token(model):
    """The chunked scan of ONE token through every Mamba layer, in the form
    the architecture's `chunk_size` Q names: inside a chunk the causal half
    of the [Q, Q] products (C.B^T over the state, then times x over the
    chunk), and per token the state's in and out (x (x) B into the chunk's
    state, h C out of the carried one): 2 FLOP a multiply-add."""
    n_m, _, _ = _kinds(model)
    heads, p, groups, n = _mamba_dims(model)
    q = model["chunk_size"]
    inside = 2 * (q / 2) * (groups * n + heads * p)
    state = 2 * 2 * heads * p * n
    return n_m * (inside + state)


def ssm_update_flops_per_token(model):
    """One decode token through every Mamba layer: decay, x (x) B in, C out
    over the [heads, P, N] state."""
    n_m, _, _ = _kinds(model)
    heads, p, _, n = _mamba_dims(model)
    return n_m * 5 * heads * p * n


def state_bytes_per_slot(model, state_itemsize=4, tail_itemsize=2):
    """One slot's recurrent state over every Mamba layer: the SSM state and
    the convolution tail."""
    n_m, _, _ = _kinds(model)
    heads, p, groups, n = _mamba_dims(model)
    conv = heads * p + 2 * groups * n
    return n_m * (heads * p * n * state_itemsize
                  + (model["conv_kernel"] - 1) * conv * tail_itemsize)


def attention_flops(model, query_key_pairs):
    """QK^T and PV over ``query_key_pairs`` (query, key) pairs a layer:
    2 matmuls x 2 FLOP x head_dim x heads, every attention layer."""
    _, n_a, _ = _kinds(model)
    return 4.0 * model["num_attention_heads"] * model["head_dim"] \
        * query_key_pairs * n_a


def required_flops(model, *, prefill_tokens, decode_tokens, logit_tokens,
                   routed_rows, prefill_pairs, decode_pairs):
    """Everything a window's tokens require.  ``routed_rows`` are the rows
    the held experts were given (all expert layers together),
    ``prefill_pairs`` / ``decode_pairs`` the (query, key) pairs attention
    had to score, ``logit_tokens`` the tokens whose logits were needed."""
    tokens = prefill_tokens + decode_tokens
    return (2.0 * dense_matmul_params(model) * tokens
            + 2.0 * head_params(model) * logit_tokens
            + 2.0 * expert_params(model) * routed_rows
            + ssd_scan_flops_per_token(model) * prefill_tokens
            + ssm_update_flops_per_token(model) * decode_tokens
            + attention_flops(model, prefill_pairs + decode_pairs))
