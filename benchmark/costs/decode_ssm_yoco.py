"""One decode step of a decoder-hybrid-decoder: selective state-space
(Mamba-1) layers, differential window and full attention, and an upper
half of gated memory units and cross layers that read the ONE full
layer's keys and values; a dense MLP in every layer. The operations it
needs and the bytes it has to move, from the configuration file's
``llama_config`` sizes.

What a step *needs*, as ``decode.py`` counts it:

- every layer's weights once, whatever the batch: the MLPs, the mamba
  mixers, the attention projections (a cross layer's without keys and
  values), the gmu's two projections; the head over the whole
  vocabulary (tied: the embedding, read once as the head), ``batch``
  embedding rows;
- a mamba layer, a live slot: its float32 state [d_inner, N] and its
  tail of K - 1 rows of d_inner, read and written. Nothing of it grows
  with the context;
- a window layer, a live slot: ``min(context, window)`` ring rows of
  keys and values;
- the full layer's ``context`` keys and values a slot, once a READING
  layer: the full layer itself and every cross layer. This is the item
  that grows with batch and context (5,120 B a token a read); a program
  that streams whole ``max_seq`` rows under a mask reads more than this
  and reads a lower share;
- a gmu layer keeps and reads nothing a slot.

Differential attention scores each query head against ONE key head (2 D
operations a key) and multiplies its probabilities with the pair's two
value heads (4 D a key): 6 D a query head a key.
"""


def mamba_weights(c: dict) -> int:
    """One mamba mixer's matrices and vectors (its pre-norm left out, as
    everywhere under ``costs/``)."""
    h = c["hidden_size"]
    di, n, k = c.get("ssm_expand", 2) * h, c.get("ssm_state", 16), c.get("ssm_conv", 4)
    r = c.get("ssm_dt_rank") or -(-h // 16)
    return h * 2 * di + (k + 1) * di + di * (r + 2 * n) + (r + 1) * di + di * n + di + di * h


def attn_weights(c: dict, cross: bool = False) -> int:
    h, d = c["hidden_size"], c["head_dim"]
    q, kv = c["n_heads"] * d, c["n_kv_heads"] * d
    return 2 * h * q + q + h + 6 * d + (0 if cross else 2 * (h + 1) * kv)


def state_bytes(c: dict, batch: float, itemsize: int = 2) -> float:
    """Bytes a step moves for the mamba layers' states (float32) and
    tails (the served dtype): each live slot's read and written."""
    di = c.get("ssm_expand", 2) * c["hidden_size"]
    n_mamba = list(c["layer_types"]).count("mamba")
    state = di * c.get("ssm_state", 16) * 4
    tail = (c.get("ssm_conv", 4) - 1) * di * itemsize
    return n_mamba * batch * 2 * (state + tail)


def decode_step(c: dict, batch: float, context: float, itemsize: int = 2) -> dict:
    """→ ``{"flops", "bytes", "weight_bytes", "cache_bytes"}`` of one
    step (``cache_bytes``: the one K/V leaf a reading layer, the rings,
    the states and tails)."""
    h, v, d = c["hidden_size"], c["vocab_size"], c["head_dim"]
    kinds = list(c["layer_types"])
    count = kinds.count
    di, n = c.get("ssm_expand", 2) * h, c.get("ssm_state", 16)
    params = (
        count("mamba") * mamba_weights(c)
        + (count("full") + count("window")) * attn_weights(c)
        + count("cross") * attn_weights(c, cross=True)
        + count("gmu") * 2 * h * di
        + c["n_layers"] * 3 * h * c["intermediate_size"] + v * h
    )
    readers = count("full") + count("cross")  # layers that read the one leaf
    ring = min(context, c["sliding_window"])
    keys = readers * context + count("window") * ring  # a slot, a step
    attn_flops = 6 * c["n_heads"] * d * keys
    scan_flops = count("mamba") * 8 * di * n  # decay, input, state, output
    flops = batch * (2 * params + attn_flops + scan_flops)
    weight_bytes = params * itemsize + batch * h * itemsize  # + embedding rows
    cache_bytes = (
        batch * keys * 2 * c["n_kv_heads"] * d * itemsize
        + state_bytes(c, batch, itemsize)
    )
    return {
        "flops": flops, "bytes": weight_bytes + cache_bytes,
        "weight_bytes": weight_bytes, "cache_bytes": cache_bytes,
    }
