"""Operations and bytes a latent-attention + sparse-expert serving step
REQUIRES, from the configuration's static shapes and the program's counters
(routed rows, touched experts, latent rows attended): the same work whatever
implements it.  Model keys are those of a configuration file.  Nothing
recomputed, padded or masked away is counted: a routed expert costs the rows
it was GIVEN, an expert's weights are read where at least one row reached
it, a latent row costs the values the model keeps of a token (not the lane
tiles a store rounds them up to).  2 FLOP a multiply-add throughout."""


def _dims(model):
    return (model["hidden_size"], model["num_attention_heads"],
            model["qk_nope_head_dim"], model["qk_rope_head_dim"],
            model["v_head_dim"], model["kv_lora_rank"])


def attention_params(model):
    """One layer's four projections: W_q, W_kva, W_kvb, W_o."""
    h, heads, dn, dr, dv, dl = _dims(model)
    return h * heads * (dn + dr) + h * (dl + dr) + dl * heads * (dn + dv) \
        + heads * dv * h


def dense_matmul_params(model):
    """Parameters that multiply EVERY token: every layer's attention
    projections, the dense layers' MLP, and of an expert layer the router
    (at its published width) and the shared experts.  The routed experts (by
    their rows) and the head (by the tokens that need logits) are counted
    apart."""
    h = model["hidden_size"]
    layers, n_dense = model["num_hidden_layers"], \
        model["first_k_dense_replace"]
    shared = 3 * h * model["n_shared_experts"] * model["moe_intermediate_size"]
    router = h * model["published"]["n_routed_experts"]
    return layers * attention_params(model) \
        + n_dense * 3 * h * model["intermediate_size"] \
        + (layers - n_dense) * (router + shared)


def expert_params(model):
    """One routed expert: three matrices hidden x intermediate."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def expert_weight_bytes(model, itemsize=2):
    return expert_params(model) * itemsize


def head_params(model):
    return model["hidden_size"] * model["vocab_size"]


def latent_bytes_per_token(model, itemsize=2):
    """What the model keeps of one token over every layer: the latent and
    the rotary key."""
    return model["num_hidden_layers"] * itemsize \
        * (model["kv_lora_rank"] + model["qk_rope_head_dim"])


def expanded_attention_flops(model, query_key_pairs):
    """QK^T over dn + dr and PV over dv for every head, every layer, over
    ``query_key_pairs`` (query, key) pairs a layer: what a run of queries
    needs when K and V per head are there (prefill)."""
    _, heads, dn, dr, dv, _ = _dims(model)
    return 2.0 * heads * (dn + dr + dv) * query_key_pairs \
        * model["num_hidden_layers"]


def absorbed_attention_flops(model, query_key_pairs):
    """Scores over the whole row (dl + dr) and the sum of latents (dl) for
    every head, every layer: what ONE query a sequence needs, for whom
    expanding every key's K and V (2 x dl x heads x (dn + dv) a key) would
    cost more than the absorbed products do (decode)."""
    _, heads, _, dr, _, dl = _dims(model)
    return 2.0 * heads * (2 * dl + dr) * query_key_pairs \
        * model["num_hidden_layers"]


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
            + expanded_attention_flops(model, prefill_pairs)
            + absorbed_attention_flops(model, decode_pairs))
