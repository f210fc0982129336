"""One decode step of a grouped-query model most of whose layers are
gated short-convolution layers (a causal depthwise convolution over the
hidden between two elementwise gates), sigmoid-routed over a chip's
share of the experts (``experts_held``): the operations it needs and
the bytes it has to move, from the configuration file's
``llama_config`` sizes.

What a step *needs*, as ``decode.py`` counts it:

- a conv layer reads its operator's weights (the input projection
  [H, 3H], the K taps, the output projection [H, H]) and, a live slot,
  its tail: K - 1 rows of ``hidden_size`` read and written. It keeps no
  keys and values, so nothing of it grows with the context;
- a full layer reads its projections and ``context`` cached keys and
  values a slot: a program that streams whole ``max_seq`` rows under a
  mask reads more than this and reads a lower share;
- the router at its published width and, of the experts held here,
  those that some token of the batch picked: a token picks
  ``experts_per_token`` of ``n_experts``, so
  ``expected_distinct_experts(n, k, batch) * held / n`` are touched a
  layer (5.15 of 8 at batch 16, top-4 of 64), and a token multiplies
  with the ``k * held / n`` (0.5) its picks find here. A program that
  reads every held expert moves more than this;
- the dense FFN of the first layers, the output head over the
  vocabulary held here (tied: the embedding, read once as the head),
  ``batch`` embedding rows.
"""

from .decode import expected_distinct_experts


def conv_weights(c: dict) -> int:
    """One conv operator's matrices and taps (norm vectors left out, as
    everywhere under ``costs/``)."""
    h = c["hidden_size"]
    return h * 3 * h + c.get("conv_taps", 3) * h + h * h


def attn_weights(c: dict) -> int:
    h, d = c["hidden_size"], c["head_dim"]
    return 2 * h * c["n_heads"] * d + 2 * h * c["n_kv_heads"] * d


def tail_bytes(c: dict, batch: float, itemsize: int = 2) -> float:
    """Bytes a step moves for the conv layers' tails: each live slot's
    K - 1 rows read and written in the served dtype."""
    n_conv = list(c["layer_types"]).count("conv")
    return n_conv * batch * 2 * (c.get("conv_taps", 3) - 1) * c["hidden_size"] * itemsize


def decode_step(c: dict, batch: float, context: float, itemsize: int = 2) -> dict:
    """→ ``{"flops", "bytes", "weight_bytes", "cache_bytes"}`` of one
    step (``cache_bytes``: the full layers' rows and the tails)."""
    h, v, d = c["hidden_size"], c["vocab_size"], c["head_dim"]
    kinds = list(c["layer_types"])
    n_conv, n_full = kinds.count("conv"), kinds.count("full")
    k_dense, n_moe = c["first_k_dense"], c["n_layers"] - c["first_k_dense"]
    f, fd = c["intermediate_size"], c["dense_intermediate"]
    n, k = c["n_experts"], c["experts_per_token"]
    held = c["experts_held"][1] if c.get("experts_held") else n
    # what every token multiplies with, and what the step reads whatever
    # the batch: operators, attention, dense FFNs, routers, the head
    fixed = (
        n_conv * conv_weights(c) + n_full * attn_weights(c)
        + k_dense * 3 * h * fd + n_moe * h * n + v * h
    )
    expert = 3 * h * f
    active = fixed + n_moe * (k * held / n) * expert
    touched = fixed + n_moe * (expected_distinct_experts(n, k, batch) * held / n) * expert
    attn_flops = n_full * 4 * c["n_heads"] * d * context  # scores and values
    conv_flops = n_conv * 2 * (c.get("conv_taps", 3) + 2) * h  # taps and the two gates
    flops = batch * (2 * active + attn_flops + conv_flops)
    weight_bytes = touched * itemsize + batch * h * itemsize  # + embedding rows
    cache_bytes = (
        batch * context * n_full * 2 * c["n_kv_heads"] * d * itemsize
        + tail_bytes(c, batch, itemsize)
    )
    return {
        "flops": flops, "bytes": weight_bytes + cache_bytes,
        "weight_bytes": weight_bytes, "cache_bytes": cache_bytes,
    }
