"""One decode step (one new token for each of ``batch`` live sequences
whose contexts average ``context`` tokens): the matmul operations it
needs and the bytes it has to read, from the configuration file's
``llama_config`` sizes. Weights are read once a step whatever the
batch; the embedding table is a gather of ``batch`` rows. What the
program reads beyond this (full ``max_seq`` cache rows, experts that no
token of the batch chose) is not needed and is not counted, so a share
of the roofline computed from these cannot be flattered by waste.
"""


def _attn_weights(c: dict) -> int:
    h = c["hidden_size"]
    if c.get("kv_lora_rank", 0):
        nh, r, rope = c["n_heads"], c["kv_lora_rank"], c["qk_rope_head_dim"]
        nope, vd = c["qk_nope_head_dim"], c["v_head_dim"]
        return h * nh * (nope + rope) + h * (r + rope) + r * nh * (nope + vd) + nh * vd * h
    q, kv = c["n_heads"] * c["head_dim"], c["n_kv_heads"] * c["head_dim"]
    return h * q + 2 * h * kv + q * h


def _mlp_mats(c: dict) -> int:
    return 2 if c.get("mlp_gateless") else 3


def expected_distinct_experts(n_experts: int, top_k: int, batch: float) -> float:
    """Experts chosen by at least one of ``batch`` tokens under uniform
    routing: the expert weights a step cannot avoid reading."""
    return n_experts * (1.0 - (1.0 - top_k / n_experts) ** batch)


def cache_bytes_per_token(c: dict, itemsize: int = 2) -> int:
    """Bytes the cache holds for one token, over all layers."""
    if c.get("kv_lora_rank", 0):
        return c["n_layers"] * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * itemsize
    return c["n_layers"] * 2 * c["n_kv_heads"] * c["head_dim"] * itemsize


def decode_step(c: dict, batch: float, context: float, itemsize: int = 2) -> dict:
    """→ ``{"flops", "bytes", "weight_bytes", "cache_bytes"}`` of one step."""
    h, v, n_layers = c["hidden_size"], c["vocab_size"], c["n_layers"]
    k_dense = c.get("first_k_dense", 0)
    n_moe = (n_layers - k_dense) if c.get("n_experts", 0) else 0
    n_plain = n_layers - n_moe
    f = c["intermediate_size"]
    fd = c.get("dense_intermediate") or f
    mats = _mlp_mats(c)
    attn = _attn_weights(c)
    # parameters every token multiplies with (active) and parameters the
    # step must read (touched)
    active = n_layers * attn + v * h  # attention + output head
    touched = active
    plain_width = fd if c.get("n_experts", 0) else f
    active += n_plain * mats * h * plain_width
    touched += n_plain * mats * h * plain_width
    if n_moe:
        e, k = c["n_experts"], c["experts_per_token"]
        shared = 3 * h * (c.get("moe_shared_intermediate") or f) if c.get("moe_shared_expert") else 0
        active += n_moe * (k * 3 * h * f + shared + h * e)
        touched += n_moe * (
            expected_distinct_experts(e, k, batch) * 3 * h * f + shared + h * e
        )
    if c.get("kv_lora_rank", 0):
        # absorbed latent attention: scores over (latent + rope), values over latent
        r, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
        attn_flops = 2 * c["n_heads"] * (2 * r + rope) * context
    else:
        attn_flops = 4 * c["n_heads"] * c["head_dim"] * context
    flops = batch * (2 * active + n_layers * attn_flops)
    weight_bytes = touched * itemsize + batch * h * itemsize  # + embedding rows
    cache_bytes = batch * context * cache_bytes_per_token(c, itemsize)
    return {
        "flops": flops, "bytes": weight_bytes + cache_bytes,
        "weight_bytes": weight_bytes, "cache_bytes": cache_bytes,
    }
