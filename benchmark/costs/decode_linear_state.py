"""One decode step of a latent-attention model most of whose layers are
linear-attention layers (a gated delta rule with a decay a channel
behind a causal convolution), routed by a group-limited router of which
this chip holds whole groups (``experts_held``): the operations it
needs and the bytes it has to move, from the configuration file's
``llama_config`` sizes.

What a step *needs*, as ``decode.py`` counts it:

- a linear layer reads its projections (q, k, v, the decay gate, the
  output gate, the output projection, beta, the convolution's taps) and,
  a live slot, its **state in float32, read and written** (heads x D x D
  x 4 bytes each way: the state does not grow with the context, and it
  cannot stay on the chip between steps) and its convolution tail
  (K - 1 rows of 3 x heads x D, read and written). A program that reads
  the state twice a step moves more than this and reads a lower share;
- a latent layer reads its projections (the head-wise gate among them)
  and ``context`` latent rows a slot;
- the router at its full width, the shared expert, and of the experts
  held here those that some token of the batch picked. Under uniform
  routing a token's ``topk_group`` eligible groups hold a held group
  with probability ``held groups x topk_group / n_group``, and it then
  picks ``experts_per_token`` of the eligible groups' experts: an
  expert's chance of a token's pick is ``k / n`` as without groups, so
  ``expected_distinct_experts(n, k, batch) * held / n`` are touched a
  layer (14.3 of 64 at batch 16, top-8 of 512) and a token multiplies
  with ``k * held / n`` of them (1.0);
- the output head over the vocabulary held here, ``batch`` embedding
  rows.
"""

from .decode import expected_distinct_experts


def linear_weights(c: dict) -> int:
    """One linear mixer's matrices (norm vectors left out, as
    everywhere under ``costs/``)."""
    h, nh, d = c["hidden_size"], c["n_heads"], c["linear_head_dim"]
    p, k = nh * d, c.get("linear_conv", 4)
    # q, k, v | decay gate, output gate, output projection | beta | taps | A_log, dt_bias
    return 3 * h * p + 3 * h * p + h * nh + k * 3 * p + nh + p


def latent_weights(c: dict) -> int:
    """One latent attention's projections (no query rank) and its
    head-wise gate."""
    h, nh = c["hidden_size"], c["n_heads"]
    r, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
    nope, vd = c["qk_nope_head_dim"], c["v_head_dim"]
    gate = h * nh if c.get("attn_gate") else 0
    return h * nh * (nope + rope) + h * (r + rope) + r * nh * (nope + vd) + nh * vd * h + gate


def state_bytes(c: dict, batch: float, itemsize: int = 2) -> float:
    """Bytes a step moves for the linear layers' states and tails:
    each live slot's state read and written in float32, its tail in the
    served dtype."""
    nh, d = c["n_heads"], c["linear_head_dim"]
    n_lin = list(c["layer_types"]).count("linear")
    tail = (c.get("linear_conv", 4) - 1) * 3 * nh * d * itemsize
    return n_lin * batch * 2 * (nh * d * d * 4 + tail)


def decode_step(c: dict, batch: float, context: float, itemsize: int = 2) -> dict:
    """→ ``{"flops", "bytes", "weight_bytes", "cache_bytes"}`` of one
    step (``cache_bytes``: the latent rows and the states together)."""
    h, v, n_layers = c["hidden_size"], c["vocab_size"], c["n_layers"]
    kinds = list(c["layer_types"])
    n_lin, n_full = kinds.count("linear"), kinds.count("full")
    k_dense, n_moe = c["first_k_dense"], n_layers - c["first_k_dense"]
    f, fd, fs = c["intermediate_size"], c["dense_intermediate"], c["moe_shared_intermediate"]
    n, k = c["n_experts"], c["experts_per_token"]
    held = c["experts_held"][1] if c.get("experts_held") else n
    nh, d = c["n_heads"], c["linear_head_dim"]
    r, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
    # what every token multiplies with, and what the step reads whatever
    # the batch: mixers, dense FFNs, routers, shared experts, the head
    fixed = (
        n_lin * linear_weights(c) + n_full * latent_weights(c)
        + k_dense * 3 * h * fd + n_moe * (h * n + 3 * h * fs) + v * h
    )
    expert = 3 * h * f
    active = fixed + n_moe * (k * held / n) * expert
    touched = fixed + n_moe * (expected_distinct_experts(n, k, batch) * held / n) * expert
    # absorbed latent attention: scores over (latent + rope), values over the latent
    attn_flops = n_full * 2 * nh * (2 * r + rope) * context
    # the delta rule a head: decay, S'^T k, the rank-one update, S^T q
    state_flops = n_lin * nh * 7 * d * d
    flops = batch * (2 * active + attn_flops + state_flops)
    weight_bytes = touched * itemsize + batch * h * itemsize  # + embedding rows
    cache_bytes = (
        batch * context * n_full * (r + rope) * itemsize + state_bytes(c, batch, itemsize)
    )
    return {
        "flops": flops, "bytes": weight_bytes + cache_bytes,
        "weight_bytes": weight_bytes, "cache_bytes": cache_bytes,
    }
