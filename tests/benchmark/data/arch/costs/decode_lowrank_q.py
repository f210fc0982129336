"""Step costs of latent attention with a low-rank query (a test
fixture, see ``reference/mla_lowrank_q.py``): ``costs/decode.py``'s
step with every layer's direct query projection ``H x Q`` replaced by
the two matrices a token multiplies with and the step reads,
``H x q_lora_rank`` and ``q_lora_rank x Q``."""

from benchmark.costs import decode


def decode_step(c: dict, batch: float, context: float, itemsize: int = 2) -> dict:
    """→ ``{"flops", "bytes", "weight_bytes", "cache_bytes"}`` of one step."""
    base = decode.decode_step(c, batch, context, itemsize)
    h, r = c["hidden_size"], c["q_lora_rank"]
    q = c["n_heads"] * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"])
    more = c["n_layers"] * (h * r + r * q - h * q)  # parameters, all layers
    weight_bytes = base["weight_bytes"] + more * itemsize
    return {
        "flops": base["flops"] + batch * 2 * more,
        "bytes": weight_bytes + base["cache_bytes"],
        "weight_bytes": weight_bytes, "cache_bytes": base["cache_bytes"],
    }
