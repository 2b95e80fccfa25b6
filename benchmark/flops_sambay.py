"""Operations and bytes a SambaY serving step REQUIRES, from the
configuration's static shapes and the program's counters: the same work
whatever implements it.  Model keys are those of a configuration file.
Nothing recomputed, padded or masked away is counted: a token costs the
layers it ENTERED (a prompt's tokens the first half and the K/V layer's K/V
projections, its last token and every decoded token all of them), a logit the head only where
it was needed, an attention pair what the two softmaxes and their sums over
the 2 hd-wide value take, a state what a live slot reads and writes.  2 FLOP
a multiply-add throughout."""


def _dims(model):
    h = model["hidden_size"]
    d_in = model["mamba_expand"] * h
    return h, d_in, model["mamba_d_state"], model["mamba_d_conv"], \
        model["mamba_dt_rank"]


def layer_counts(model):
    """{kind: layers}: Mamba every second layer up to the middle one, window
    attention between them, the K/V layer, then GMU and cross attention."""
    n = model["num_hidden_layers"]
    return {"mamba": n // 4 + 1, "window": n // 4, "full": 1,
            "gmu": n // 4 - 1, "cross": n // 4 - 1}


def mixer_params(model):
    """{kind: the matrices of one mixer that multiply a token}."""
    h, d_in, n, _, r = _dims(model)
    hd = h // model["num_attention_heads"]
    kv = model["num_key_value_heads"] * hd
    return {"mamba": h * 2 * d_in + d_in * (r + 2 * n) + r * d_in + d_in * h,
            "window": 2 * h * h + 2 * h * kv, "full": 2 * h * h + 2 * h * kv,
            "gmu": 2 * h * d_in, "cross": 2 * h * h}


def mlp_params(model):
    return 3 * model["hidden_size"] * model["intermediate_size"]


def kv_projection_params(model):
    """W_k and W_v of the K/V layer: the one part of it a prompt's every
    token needs (the rows of the store)."""
    h = model["hidden_size"]
    return 2 * h * model["num_key_value_heads"] \
        * (h // model["num_attention_heads"])


def self_decoder_params(model):
    """What EVERY prompt token enters: the Mamba and window layers (layers
    0 .. L / 2) with their MLPs, and the K/V layer's two K/V projections."""
    c, p = layer_counts(model), mixer_params(model)
    return sum(c[k] * (p[k] + mlp_params(model))
               for k in ("mamba", "window")) + kv_projection_params(model)


def cross_decoder_params(model):
    """What a prompt's LAST token and every decoded token enter besides:
    the K/V layer's queries, output projection and MLP (nothing reads
    another token's) and layers L / 2 + 2 .. L - 1."""
    c, p = layer_counts(model), mixer_params(model)
    return p["full"] - kv_projection_params(model) + mlp_params(model) \
        + sum(c[k] * (p[k] + mlp_params(model)) for k in ("gmu", "cross"))


def head_params(model):
    return model["hidden_size"] * model["vocab_size"]


def shared_kv_bytes_per_token(model, itemsize=2):
    """K and V of ONE token in the one store: read once by each of the
    layers that attend it."""
    hd = model["hidden_size"] // model["num_attention_heads"]
    return 2 * model["num_key_value_heads"] * hd * itemsize


def attention_flops(model, pairs):
    """``pairs`` (query, key) pairs summed over the attention layers that
    scored them: q . k for every head (2 hd) and, a query PAIR, two sums over
    the 2 hd-wide value (2 x 2 x 2 hd)."""
    hd = model["hidden_size"] // model["num_attention_heads"]
    heads = model["num_attention_heads"]
    return pairs * (heads * 2 * hd + (heads // 2) * 2 * 2 * 2 * hd)


def scan_flops_per_token(model):
    """One token through every Mamba layer's recurrence: dt A, the decay, dt
    u B in, C out over [D, N] (6 a state element), the convolution and the
    D u skip."""
    _, d_in, n, k, _ = _dims(model)
    return layer_counts(model)["mamba"] * d_in * (6 * n + 2 * k + 2)


def scan_bytes_per_token(model):
    """What the float32 recurrence moves a prompt token and Mamba layer: u
    and dt in, y out (D each), B and C (N each); the state passes on in
    fast memory."""
    _, d_in, n, _, _ = _dims(model)
    return layer_counts(model)["mamba"] * 4 * (3 * d_in + 2 * n)


def state_bytes_per_slot(model, state_itemsize=4, tail_itemsize=2):
    """One slot's recurrent state over every Mamba layer: the SSM state and
    the convolution tail."""
    _, d_in, n, k, _ = _dims(model)
    return layer_counts(model)["mamba"] * (
        d_in * n * state_itemsize + (k - 1) * d_in * tail_itemsize)


def scan_required_s(model, prefill_tokens, peak_flops, peak_bytes_per_s):
    """The least seconds the chip could take for the scans of
    ``prefill_tokens``: the greater of FLOPs over peak and bytes over the
    memory's rate (the bytes, by 30 x)."""
    return prefill_tokens * max(scan_flops_per_token(model) / peak_flops,
                                scan_bytes_per_token(model)
                                / peak_bytes_per_s)


def required_flops(model, *, prefill_tokens, cross_tokens, decode_tokens,
                   logit_tokens, prefill_pairs, decode_pairs):
    """Everything a window's tokens require.  ``prefill_tokens`` entered the
    first half and the K/V layer, ``cross_tokens`` of them (a prompt's last)
    the second half too; ``decode_tokens`` every layer; ``logit_tokens`` the
    head; ``prefill_pairs`` / ``decode_pairs`` the (query, key) pairs summed
    over the attention layers that scored them."""
    lower, upper = self_decoder_params(model), cross_decoder_params(model)
    return (2.0 * lower * (prefill_tokens + decode_tokens)
            + 2.0 * upper * (cross_tokens + decode_tokens)
            + 2.0 * head_params(model) * logit_tokens
            + scan_flops_per_token(model) * (prefill_tokens + decode_tokens)
            + attention_flops(model, prefill_pairs + decode_pairs))
