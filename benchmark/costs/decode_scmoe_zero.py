"""One decode step of a latent-attention model whose layer is several
(attention, dense FFN) sublayers with one expert branch across them,
routed over ``n_experts + zero_experts`` outputs of which the last
``zero_experts`` are identity experts and ``experts_held`` of the real
ones are on this chip: the operations it needs and the bytes it has to
read, from the configuration file's ``llama_config`` sizes.

What a step *needs*, as ``decode.py`` counts it for a layer of one
attention and one MLP:

- a layer reads ``sublayers`` latent attentions' weights (the query
  rank's two projections among them) and ``context`` latent rows a slot
  a sublayer: each sublayer has a cache row of its own. A program that
  streams whole ``max_seq`` rows reads more than this and reads a lower
  share;
- ``sublayers`` dense FFNs of ``dense_intermediate`` and the router at
  its full width ``n_experts + zero_experts``;
- of the experts held here, only those that some token of the batch
  picked: a token picks ``experts_per_token`` of the router's outputs,
  so under uniform routing ``expected_distinct_experts(n + z, k, batch)
  * held / (n + z)`` are touched a layer (3.6 of 16 at batch 16, top-12
  of 768). A token multiplies with the ``k * held / (n + z)`` experts
  its picks find here (0.25);
- an identity expert has no weights: a pick of one reads nothing and
  costs ``2 * hidden_size`` operations (the gate times the token, added
  on); ``k * z / (n + z)`` of a token's picks are such (4 of 12);
- the output head over the vocabulary held here, ``batch`` embedding
  rows.
"""

from .decode import expected_distinct_experts


def attn_weights(c: dict) -> int:
    """One latent-attention sublayer's projections (norm vectors left
    out, as everywhere under ``costs/``)."""
    h, nh = c["hidden_size"], c["n_heads"]
    rq, r, rope = c["q_lora_rank"], c["kv_lora_rank"], c["qk_rope_head_dim"]
    nope, vd = c["qk_nope_head_dim"], c["v_head_dim"]
    return (
        h * rq + rq * nh * (nope + rope) + h * (r + rope)
        + r * nh * (nope + vd) + nh * vd * h
    )


def decode_step(c: dict, batch: float, context: float, itemsize: int = 2) -> dict:
    """→ ``{"flops", "bytes", "weight_bytes", "cache_bytes"}`` of one step."""
    h, v, n_layers, subs = c["hidden_size"], c["vocab_size"], c["n_layers"], c["sublayers"]
    f, fd = c["intermediate_size"], c["dense_intermediate"]
    n, z, k = c["n_experts"], c.get("zero_experts", 0), c["experts_per_token"]
    held = c["experts_held"][1] if c.get("experts_held") else n
    r, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
    # what every token multiplies with, and what the step reads whatever
    # the batch: the sublayers, the router, the head
    fixed = n_layers * (subs * (attn_weights(c) + 3 * h * fd) + h * (n + z)) + v * h
    expert = 3 * h * f
    active = fixed + n_layers * (k * held / (n + z)) * expert
    touched = fixed + n_layers * (
        expected_distinct_experts(n + z, k, batch) * held / (n + z)
    ) * expert
    # absorbed latent attention a sublayer: scores over (latent + rope),
    # values over the latent
    attn_flops = n_layers * subs * 2 * c["n_heads"] * (2 * r + rope) * context
    zero_flops = n_layers * (k * z / (n + z)) * 2 * h
    flops = batch * (2 * active + attn_flops + zero_flops)
    weight_bytes = touched * itemsize + batch * h * itemsize  # + embedding rows
    cache_bytes = batch * context * n_layers * subs * (r + rope) * itemsize
    return {
        "flops": flops, "bytes": weight_bytes + cache_bytes,
        "weight_bytes": weight_bytes, "cache_bytes": cache_bytes,
    }
