"""One decode step of a grouped-query model of layer groups (full and
window layers whose query head counts differ, per-head gates, a dense
first layer, a chip's share of the experts): the operations it needs
and the bytes it has to read, from the configuration file's
``llama_config`` sizes.

What a step *needs*, as ``decode.py`` counts it for one kind of layer:

- a full layer reads ``context`` cached keys and values a slot, a
  window layer ``min(context, sliding_window)``: a program that streams
  whole ``max_seq`` rows, or a whole ring, under a mask reads more than
  this and reads a lower share. Each kind multiplies them with its own
  number of query heads (``n_heads`` | ``swa_n_heads``);
- of the experts held here (``experts_held``), only those that some
  token of the batch picked, ``expected_distinct_experts`` of them under
  uniform routing (never more than are held): a token picks
  ``experts_per_token`` of ``n_experts``, so ``held * (1 - (1 - k / n)
  ** batch)`` are touched a layer (15.1 of 32 at batch 16, top-10 of
  256). A token multiplies with the ``k * held / n`` experts its picks
  find here;
- the router at its published width, the shared expert, the dense first
  layers, every attention projection (the gate included), the output
  head over the vocabulary held here, ``batch`` embedding rows.
"""

from .decode import expected_distinct_experts


def _attn_weights(c: dict, kind: str) -> int:
    h, d = c["hidden_size"], c["head_dim"]
    nh = c["swa_n_heads"] if kind == "window" else c["n_heads"]
    gate = h * nh if c.get("attn_gate") else 0
    return 2 * h * nh * d + 2 * h * c["n_kv_heads"] * d + gate


def decode_step(c: dict, batch: float, context: float, itemsize: int = 2) -> dict:
    """→ ``{"flops", "bytes", "weight_bytes", "cache_bytes"}`` of one step."""
    h, v, d = c["hidden_size"], c["vocab_size"], c["head_dim"]
    k_dense = c.get("first_k_dense", 0)
    f = c["intermediate_size"]
    fd = c.get("dense_intermediate") or f
    e, k = c["n_experts"], c["experts_per_token"]
    held = c["experts_held"][1] if c.get("experts_held") else e
    shared = 3 * h * (c.get("moe_shared_intermediate") or f) if c.get("moe_shared_expert") else 0
    # the held share of the experts some token of the batch picked
    touched_experts = min(held, expected_distinct_experts(e, k, batch) * held / e)
    active = touched = v * h  # the output head
    attn_flops = cache_rows = 0.0
    for i, kind in enumerate(c["layer_types"]):
        w = _attn_weights(c, kind)
        active += w
        touched += w
        rows = min(context, c["sliding_window"]) if kind == "window" else context
        nh = c["swa_n_heads"] if kind == "window" else c["n_heads"]
        attn_flops += 4 * nh * d * rows  # scores and values
        cache_rows += rows
        if i < k_dense:
            active += 3 * h * fd
            touched += 3 * h * fd
        else:
            active += h * e + shared + (k * held / e) * 3 * h * f
            touched += h * e + shared + touched_experts * 3 * h * f
    flops = batch * (2 * active + attn_flops)
    weight_bytes = touched * itemsize + batch * h * itemsize  # + embedding rows
    cache_bytes = batch * cache_rows * 2 * c["n_kv_heads"] * d * itemsize
    return {
        "flops": flops, "bytes": weight_bytes + cache_bytes,
        "weight_bytes": weight_bytes, "cache_bytes": cache_bytes,
    }
