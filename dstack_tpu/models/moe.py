"""Sparse Mixture-of-Experts MLP with expert parallelism over ``ep``.

One router (:func:`select`), two forms of the expert FFNs behind it,
and one rule that chooses between them (:func:`reads_picked_experts`).

**The capacity form** (GShard/Switch-style, designed for XLA rather
than translated from a CUDA/torch grouped-GEMM MoE): routing produces a
one-hot *dispatch* tensor [B, T, E, C] (capacity-bounded), the token →
expert shuffle and the return combine are plain einsums, and the expert
FFNs are one batched einsum over the stacked expert dim. Sharding the
expert dim over ``ep`` (and tokens over ``dp``/``fsdp``) makes XLA lower
the dispatch einsums to ``all_to_all`` collectives on ICI — no manual
communication code, static shapes (a token over an expert's capacity is
dropped), everything MXU-shaped. It multiplies its slots through EVERY
expert of the stack whatever the routing: right where most experts are
picked by some token (training, prefill, a wide batch over few
experts), and the only form under ``rules`` / a mesh.

**The picked form** (:func:`_picked_experts`): a decode step routes a
handful of tokens, and of a chip's experts most are picked by nobody.
The distinct experts that some valid token picked are sorted to the
front of a list and ONE ``fori_loop`` of a dynamic trip count runs all
the call's tokens through one such expert's three matrices a trip
(one ``dynamic_slice`` of each stack), adding gate · output into a
float32 accumulator; no other expert's weights are read. Dropless, the
same sum Σ_j gate_j · FFN_{e_j}(x) as the combine einsum.

**The rule** is decided at trace time from what a call can see, never
by a flag: serving (``rules is None``), a shape at which the capacity
form is dropless too (``cap == T``: so the two agree whatever
``capacity_factor`` is), the plain weight forms (stacks in the model's
dtype, SwiGLU, the gate on the combine side), and then the bytes each
form streams a call. The capacity form streams the stack: ``count``
experts of ``expert_bytes`` (one expert's three matrices). The picked
form makes a trip for each expert some token picked, ``count`` times
:func:`picked_share` of them expected (``1 - (1 - k/E)^N`` over the
router's width ``E`` and the call's ``N`` tokens), and a trip streams
its expert and pays :data:`TRIP_BYTES` beside it. The picked form is
taken where that is the smaller number: at few tokens over a wide
router, and the sooner the larger an expert is beside a trip's fixed
cost; a toy expert never takes it.

Aux losses follow Switch Transformer: load-balance (E · Σ_e f_e·p_e) and
router z-loss; the router runs in f32 for softmax stability.

The reference framework ships no MoE (parallelism is user-space there);
this is part of the in-repo TPU compute plane. Expert-parallel axis
vocabulary: parallel/mesh.py ``ep``; rules map "experts" → "ep"
(parallel/sharding.py).
"""

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from dstack_tpu.parallel.sharding import ShardingRules, constrain


#: a layer's three stacks of expert matrices
EXPERT_STACKS = ("w_gate", "w_up", "w_down")


class Row(NamedTuple):
    """``stack[index]`` of a leaf stacked over layers, not yet taken: how
    a layer scan hands :func:`moe_mlp` an expert stack it is to read a
    few experts of. A loop inside a scanned layer body cannot read a
    slice of the scan's: the compiler copies the layer's whole stack
    out for it, a layer a step (device-free at 16 x 6144 x 2048: 1.21 GB
    of ``temp``, three times what the capacity form reads). The picked
    form takes ``stack[index, e]`` in one slice instead."""

    stack: jax.Array  # [L, count, ...]
    index: jax.Array  # the layer's row, a traced scalar


def _matrix_of(leaf, e) -> jax.Array:
    """Expert ``e``'s matrix, in ONE slice of what holds it."""
    at = (e,)
    if isinstance(leaf, Row):
        leaf, at = leaf.stack, (leaf.index, e)
    return jax.lax.dynamic_slice(
        leaf, (*at, 0, 0), (1,) * len(at) + leaf.shape[len(at):]
    ).reshape(leaf.shape[len(at):])


def expert_capacity(
    seq_len: int, n_experts: int, experts_per_token: int, capacity_factor: float
) -> int:
    """Per-expert token slots per batch row (static; multiple of 8 for
    lane-friendly layouts)."""
    raw = capacity_factor * seq_len * experts_per_token / n_experts
    cap = max(8, int(-(-raw // 8) * 8))
    return min(cap, seq_len)


def _group_limit(
    sel: jax.Array,  # [B, T, E] selection scores (≥ 0 where eligible)
    groups: tuple,  # (n_group, topk_group)
    score: str,
) -> jax.Array:
    """DeepSeek group-limited top-k: experts partition into ``n_group``
    groups; only the best ``topk_group`` groups stay eligible, the rest
    are zeroed (HF's ``masked_fill(~mask, 0)`` — exact parity incl. its
    quirk that a zeroed slot can outrank a genuinely negative score).
    Group score: max member (V2 softmax) or top-2 sum (V3 sigmoid)."""
    gs, gmask = _eligible_groups(sel, groups, score)
    return (gs * gmask[..., None]).reshape(sel.shape)


def _eligible_groups(sel: jax.Array, groups: tuple, score: str) -> tuple:
    """→ (``sel`` by group [B, T, n_group, E / n_group], the mask of the
    ``topk_group`` best groups [B, T, n_group] in ``sel``'s dtype)."""
    n_group, topk_group = groups
    e = sel.shape[-1]
    gs = sel.reshape(*sel.shape[:-1], n_group, e // n_group)
    if score == "sigmoid":  # V3: sum of the group's top-2 biased scores
        top2, _ = jax.lax.top_k(gs, 2)
        g_score = top2.sum(axis=-1)
    else:  # V2 group_limited_greedy: best member
        g_score = gs.max(axis=-1)
    _, gidx = jax.lax.top_k(g_score, topk_group)  # [B, T, topk_group]
    return gs, jax.nn.one_hot(gidx, n_group, dtype=sel.dtype).sum(axis=-2)


def select(
    x: jax.Array,  # [B, T, H] (model dtype)
    w_router: jax.Array,  # [H, E]
    experts_per_token: int,
    renorm: bool = False,
    sigmoid: bool = False,
    score: str = "softmax",
    groups: tuple = (),
    bias: Optional[jax.Array] = None,
    routed_scale: float = 1.0,
    pre_bias: Optional[jax.Array] = None,
    topk_softmax: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The router's selection alone → (logits [B,T,E] f32, probs
    [B,T,E] f32, expert_idx [B,T,k], gate_vals [B,T,k] f32), over the
    router's whole width; what both forms of the expert FFNs start
    from. The arguments are :func:`router`'s."""
    logits = jnp.einsum(
        "bth,he->bte", x, w_router.astype(x.dtype), preferred_element_type=jnp.float32
    )  # [B, T, E] f32
    if pre_bias is not None:  # a true LINEAR router (gpt-oss)
        logits = logits + pre_bias.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    if topk_softmax:
        # gpt-oss: select by raw logit, then softmax over ONLY the
        # selected logits (HF GptOssTopKRouter)
        top_logits, expert_idx = jax.lax.top_k(logits, experts_per_token)
        gate_vals = jax.nn.softmax(top_logits, axis=-1)
    elif sigmoid:
        top_logits, expert_idx = jax.lax.top_k(logits, experts_per_token)
        gate_vals = jax.nn.sigmoid(top_logits)
    else:
        scores = jax.nn.sigmoid(logits) if score == "sigmoid" else probs
        sel = scores if bias is None else scores + bias
        if groups:
            sel = _group_limit(sel, groups, score)
        sel_vals, expert_idx = jax.lax.top_k(sel, experts_per_token)  # [B,T,k]
        gate_vals = (
            jnp.take_along_axis(scores, expert_idx, axis=-1)
            if (bias is not None or groups) else sel_vals
        )
    if renorm:
        denom = jnp.sum(gate_vals, axis=-1, keepdims=True)
        if score == "sigmoid":
            denom = denom + 1e-20  # HF V3 epsilon
        gate_vals = gate_vals / denom
    if routed_scale != 1.0:
        gate_vals = gate_vals * routed_scale
    return logits, probs, expert_idx, gate_vals


def _routing_aux(
    logits, probs, expert_idx, gate_vals, held, valid, zero,
    groups=(), score="softmax", bias=None,
):
    """What a routing reports beside its sum, the same for both forms
    of the expert FFNs (``expert_idx`` over the router's whole width):
    the Switch losses and, for a chip's share and for identity experts,
    the counts and the gate sum :func:`router` documents. Under
    group-limited routing a chip's share is whole groups, and ``aux
    ["group_hit"]`` counts the (``valid``) tokens one of whose eligible
    groups is held here: the tokens an expert-parallel layer would
    send this chip."""
    # Switch aux losses (f32): load balance + router z-loss
    e = logits.shape[-1]
    top1 = jax.nn.one_hot(expert_idx[..., 0], e, dtype=jnp.float32)
    frac_tokens = top1.mean(axis=(0, 1))  # fraction routed (top-1) per expert
    frac_probs = probs.mean(axis=(0, 1))
    balance = e * jnp.sum(frac_tokens * frac_probs)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    aux = {"balance": balance, "z": z}
    if held:
        landed = (expert_idx >= held[0]) & (expert_idx < held[0] + held[1])
        if valid is not None:
            landed = landed & valid[..., None]
        aux["held_picks"] = jnp.sum(landed).astype(jnp.int32)
    if held and groups:
        # the selection's own scores and group choice, made again (the
        # compiler folds the two)
        scores = jax.nn.sigmoid(logits) if score == "sigmoid" else probs
        sel = scores if bias is None else scores + bias
        size = logits.shape[-1] // groups[0]
        mine = _eligible_groups(sel, groups, score)[1][
            ..., held[0] // size : (held[0] + held[1]) // size
        ]
        hit = jnp.sum(mine, axis=-1) > 0
        if valid is not None:
            hit = hit & valid
        aux["group_hit"] = jnp.sum(hit).astype(jnp.int32)
    if zero:
        is_zero = expert_idx >= e - zero
        aux["zero_gate"] = jnp.sum(jnp.where(is_zero, gate_vals, 0.0), axis=-1)
        if valid is not None:
            is_zero = is_zero & valid[..., None]
        aux["zero_picks"] = jnp.sum(is_zero).astype(jnp.int32)
    return aux


def router(
    x: jax.Array,  # [B, T, H] (model dtype)
    w_router: jax.Array,  # [H, E]
    n_experts: int,
    experts_per_token: int,
    capacity: int,
    renorm: bool = False,  # Mixtral: renormalize top-k gates to sum 1
    sigmoid: bool = False,  # Llama4: gates are sigmoid(top-k logit)
    score: str = "softmax",  # full-score fn: "softmax" (V2) | "sigmoid" (V3)
    groups: tuple = (),  # DeepSeek (n_group, topk_group) group limiting
    bias: Optional[jax.Array] = None,  # V3 e_score_correction_bias [E]
    routed_scale: float = 1.0,  # DeepSeek routed_scaling_factor
    pre_bias: Optional[jax.Array] = None,  # gpt-oss linear router bias [E]
    topk_softmax: bool = False,  # gpt-oss: gates = softmax over top-k logits
    held: tuple = (),  # (first, count): the experts whose weights are here
    valid: Optional[jax.Array] = None,  # [B, T] bool: real tokens (held_picks)
    zero: int = 0,  # the router's last outputs that are identity experts
) -> tuple[jax.Array, jax.Array, dict]:
    """Top-k routing → (dispatch [B,T,E,C] one-hot, combine [B,T,E,C], aux).

    ``held``: a chip's share of the layer. Scores, selection and gate
    values are over all ``n_experts`` as ever; dispatch and combine are
    built for the ``count`` held experts only ([B,T,count,C]), so a pick
    that fell on an absent expert takes no slot and adds nothing: its
    part of the sum is another chip's. ``aux["held_picks"]`` counts the
    picks that landed here (of the ``valid`` tokens, where given).

    ``zero``: the last ``zero`` of the router's outputs are experts
    without weights that return their input (``w_router`` is
    ``n_experts + zero`` wide). They are scored, biased, selected and
    gated with the others; a pick of one takes no capacity slot, and
    ``aux["zero_gate"]`` [B, T] f32 is the sum of a token's gates that
    fell on them (the caller adds that times the token), ``aux
    ["zero_picks"]`` their count.

    Each batch row is a routing group: capacity slots are assigned in
    sequence order per expert (cumsum positions), tokens overflowing an
    expert's capacity are dropped for that expert (their combine weight
    is zero — the residual stream carries them unchanged).

    ``sigmoid``: experts are still chosen by top-k logit (softmax is
    monotonic, so the selection is identical), but the gate value is
    sigmoid(logit) — Llama4's router scoring.

    DeepSeek variants (HF deepseek_v2/v3 parity): ``score="sigmoid"``
    scores every expert with sigmoid(logit) instead of softmax; ``bias``
    shifts scores for *selection only* (gate values stay unbiased);
    ``groups`` restricts selection to the best expert groups; gates are
    finally scaled by ``routed_scale``.
    """
    logits, probs, expert_idx, gate_vals = select(
        x, w_router, experts_per_token, renorm=renorm, sigmoid=sigmoid,
        score=score, groups=groups, bias=bias, routed_scale=routed_scale,
        pre_bias=pre_bias, topk_softmax=topk_softmax,
    )

    # Build per-choice one-hot assignments and capacity positions.
    # Choice order gives earlier (higher-gate) choices slot priority.
    e_here = logits.shape[-1]
    # the experts dispatched to: the held ones, else all that have weights
    here = held or ((0, e_here - zero) if zero else ())
    if here:
        # an index outside [0, count) one-hots to a row of zeros
        e_here = here[1]
        expert_idx = expert_idx - here[0]
    slots = (*logits.shape[:-1], e_here)
    dispatch = jnp.zeros((*slots, capacity), x.dtype)  # [B,T,E,C]
    combine = jnp.zeros((*slots, capacity), x.dtype)
    used = jnp.zeros(slots, jnp.int32)  # [B,T,E] cumulative one-hots
    for j in range(experts_per_token):
        onehot = jax.nn.one_hot(expert_idx[..., j], e_here, dtype=jnp.int32)
        # slot of this token in expert e's capacity buffer: this-choice
        # tokens before it in the sequence, offset past ALL assignments
        # from earlier (higher-priority) choices
        pos = jnp.cumsum(onehot, axis=1) - 1 + used.sum(axis=1, keepdims=True)
        within = (pos < capacity) & (onehot > 0)
        slot_oh = jax.nn.one_hot(
            jnp.clip(pos, 0, capacity - 1), capacity, dtype=x.dtype
        )  # [B,T,E,C]
        sel = slot_oh * within[..., None].astype(x.dtype) * onehot[..., None].astype(x.dtype)
        dispatch = dispatch + sel
        combine = combine + sel * gate_vals[..., j, None, None].astype(x.dtype)
        used = used + onehot

    if here:
        expert_idx = expert_idx + here[0]
    aux = _routing_aux(
        logits, probs, expert_idx, gate_vals, held, valid, zero,
        groups=groups, score=score, bias=bias,
    )
    return dispatch, combine, aux


#: what a trip of the picked form's loop costs beside its expert's own
#: bytes (the loop's condition, ``order[i]``, the gate column's slice, two
#: fusions that each start their stream from nothing, and the call's sort
#: and gate table spread over its trips), written as the bytes the chip
#: streams in that time. Read on a v5e (PERF.md §6, PR 46) from one routed
#: layer call of 16 x 1 tokens in a layer scan, both forms, as
#: ``loop's time / trips x the capacity form's bytes a µs - expert_bytes``:
#: 4.2 MB at 8 held of a 64-wide top-4 router (18.9 MB an expert: 168.7 µs
#: against 213.3) and 5.1 MB at 64 of 64, top-6 (17.3 MB: 1528.7 µs
#: against 1492.7, the loop BEHIND); the constant is the slower reading,
#: so that 50.7 trips for a fifth of the bytes keep the batched einsums.
#: Eleven more readings at the other cells' shapes and at 2-80 tokens
#: (3.8-13.8 MB, the high ones where a call makes few trips and the loop
#: is far ahead) fall on the side of the rule this value puts them.
TRIP_BYTES = 5_100_000


def picked_share(n_tokens: int, router_width: int, experts_per_token: int) -> float:
    """Expected share of a stack's experts that at least one of
    ``n_tokens`` picks, each token choosing ``experts_per_token`` of the
    router's ``router_width`` outputs uniformly: E · (1 − (1 − k/E)^N)
    distinct outputs, of which a stack of ``count`` experts sees
    count/E, over ``count``: the stack's size cancels."""
    return 1.0 - (1.0 - experts_per_token / router_width) ** n_tokens


def _experts_and_bytes(layer: dict) -> tuple:
    """(experts in the layer's stacks, bytes of one expert's three
    matrices), from the stacks as a layer holds them: [count, ., .],
    stacked over layers, or a :class:`Row` of that."""
    stacks = [
        leaf.stack if isinstance(leaf, Row) else leaf
        for leaf in (layer[w] for w in EXPERT_STACKS)
    ]
    return stacks[0].shape[-3], sum(
        a.shape[-2] * a.shape[-1] * jnp.dtype(a.dtype).itemsize for a in stacks
    )


def reads_picked_experts(
    layer: dict,  # the layer's leaves (arrays or shapes)
    batch: int,
    seq_len: int,
    n_experts: int,
    experts_per_token: int,
    capacity_factor: float,
    rules: Optional[ShardingRules],
    sigmoid_input: bool = False,
    act: str = "silu",
) -> bool:
    """Whether a call of :func:`moe_mlp` takes the picked form (module
    docstring: the rule), from its shapes and the layer's leaves alone."""
    plain = (
        all(w in layer for w in EXPERT_STACKS)
        and "b_gate" not in layer and "b_down_e" not in layer
        and act == "silu" and not sigmoid_input
    )
    dropless = seq_len == expert_capacity(
        seq_len, n_experts, experts_per_token, capacity_factor
    )
    if not (rules is None and plain and dropless):
        return False
    count, expert_bytes = _experts_and_bytes(layer)
    trips = count * picked_share(
        batch * seq_len, layer["w_router"].shape[-1], experts_per_token
    )
    return trips * (expert_bytes + TRIP_BYTES) < count * expert_bytes


def _picked_experts(
    x: jax.Array,  # [B, T, H]
    layer: dict,  # w_gate / w_up [count, H, F], w_down [count, F, H]
    expert_idx: jax.Array,  # [B, T, k] over the router's whole width
    gate_vals: jax.Array,  # [B, T, k] f32
    here: tuple,  # (first, count) of the router's outputs the stacks hold
    valid: Optional[jax.Array],  # [B, T] bool: real tokens
) -> tuple[jax.Array, jax.Array]:
    """Σ_j gate_j · FFN_{e_j}(x) over the picks that fell on a stacked
    expert → (output [B,T,H], the number of distinct experts read).

    A pick outside ``here``, of an identity expert, or of a token
    ``valid`` marks dead one-hots to zeros: it adds nothing and puts no
    expert on the list. The picked experts come first in ``order`` (a
    stable sort), and a loop of ``n`` trips reads one expert's three
    matrices a trip: all tokens go through it, a token that did not
    pick it at gate 0. Accumulated in float32."""
    b, t, h = x.shape
    first, count = here
    onehot = jax.nn.one_hot(expert_idx - first, count, dtype=jnp.float32)
    if valid is not None:
        onehot = onehot * valid[..., None, None].astype(jnp.float32)
    # [N, count] f32, elementwise: no matmul rounds a gate
    gates = jnp.sum(onehot * gate_vals[..., None], axis=2).reshape(b * t, count)
    picked = jnp.sum(onehot, axis=(0, 1, 2)) > 0
    order = jnp.argsort(~picked, stable=True).astype(jnp.int32)
    n = jnp.sum(picked).astype(jnp.int32)
    rows = x.reshape(b * t, h)

    def one_expert(i, acc):
        e = order[i]
        w_gate, w_up, w_down = (_matrix_of(layer[w], e) for w in EXPERT_STACKS)
        inner = jax.nn.silu(rows @ w_gate) * (rows @ w_up)
        y = jnp.dot(inner, w_down, preferred_element_type=jnp.float32)
        return acc + jax.lax.dynamic_slice_in_dim(gates, e, 1, axis=1) * y

    acc = jax.lax.fori_loop(
        0, n, one_expert, jnp.zeros((b * t, h), jnp.float32)
    )
    return acc.astype(x.dtype).reshape(b, t, h), n


def _every_expert(
    x, layer, n_experts, experts_per_token, cap, mesh, rules, routing,
    sigmoid_input, act, act_limit, held, valid, zero,
):
    """The capacity form (module docstring) → (the stacked experts' sum
    [B,T,H], aux). ``routing``: :func:`router`'s keyword arguments."""
    def qw(name):
        """Expert weight, resolving the int8 form: returns (w, scale or
        None). The per-output-channel scale multiplies the einsum OUTPUT
        (exact under the contraction — models/quant.py)."""
        w = layer.get(name)
        if isinstance(w, Row):  # (the engine hands rows to the picked form)
            w = w.stack[w.index]
        if w is not None:
            return w, None
        return layer[name + "_q"].astype(x.dtype), layer[name + "_s"]

    dispatch, combine, aux = router(
        x, layer["w_router"], n_experts, experts_per_token, cap,
        held=held, valid=valid, zero=zero, **routing,
    )
    if sigmoid_input:
        # move the gate onto the dispatch side: expert input is g·x,
        # combine returns the raw expert output
        dispatch, combine = combine, dispatch
    # (a capture finds a chip's share of the experts under this name)
    with jax.named_scope("dtpu.moe_held" if held else "dtpu.moe"):
        # token shuffle: [B,T,E,C] × [B,T,H] → [E,B,C,H]; ep-sharding the
        # expert dim makes this the all_to_all dispatch
        xe = jnp.einsum("btec,bth->ebch", dispatch, x)
        if rules is not None:
            xe = constrain(xe, rules, "experts", "batch_noexp", None, None, mesh=mesh)
        wg, sg = qw("w_gate")
        wu, su = qw("w_up")
        g = jnp.einsum("ebch,ehf->ebcf", xe, wg)
        u = jnp.einsum("ebch,ehf->ebcf", xe, wu)
        if sg is not None:  # scales are [E, F]: broadcast over (b, c)
            g = g * sg[:, None, None, :].astype(g.dtype)
            u = u * su[:, None, None, :].astype(u.dtype)
        if "b_gate" in layer:  # gpt-oss expert biases [E, F]
            g = g + layer["b_gate"][:, None, None, :].astype(g.dtype)
            u = u + layer["b_up_e"][:, None, None, :].astype(u.dtype)
        if rules is not None:
            g = constrain(g, rules, "experts", "batch_noexp", None, "mlp", mesh=mesh)
        if act == "oai_glu":
            # gpt-oss clamped glu: (up+1) * gate * sigmoid(1.702 * gate),
            # gate clamped above, up clamped both sides (HF GptOssExperts)
            g = jnp.minimum(g, act_limit)
            u = jnp.clip(u, -act_limit, act_limit)
            inner = (u + 1.0) * (g * jax.nn.sigmoid(1.702 * g))
        else:
            inner = jax.nn.silu(g) * u
        wd, sd = qw("w_down")
        y = jnp.einsum("ebcf,efh->ebch", inner, wd)
        if sd is not None:  # [E, H]
            y = y * sd[:, None, None, :].astype(y.dtype)
        if "b_down_e" in layer:
            y = y + layer["b_down_e"][:, None, None, :].astype(y.dtype)
        if rules is not None:
            y = constrain(y, rules, "experts", "batch_noexp", None, None, mesh=mesh)
        out = jnp.einsum("btec,ebch->bth", combine, y)
    return out, aux


def moe_mlp(
    x: jax.Array,  # [B, T, H] — the *normed* hidden states
    layer: dict,  # w_router [H,E], w_gate/w_up [E,H,F], w_down [E,F,H]
    n_experts: int,
    experts_per_token: int,
    capacity_factor: float,
    mesh: Optional[Mesh],
    rules: Optional[ShardingRules],
    renorm: bool = False,
    sigmoid_input: bool = False,  # Llama4: sigmoid gate scales the INPUT
    score: str = "softmax",  # DeepSeek-V3: "sigmoid" full-score routing
    groups: tuple = (),  # DeepSeek (n_group, topk_group)
    routed_scale: float = 1.0,  # DeepSeek routed_scaling_factor
    topk_softmax: bool = False,  # gpt-oss router (gates softmax over top-k)
    act: str = "silu",  # "silu" SwiGLU | "oai_glu" gpt-oss clamped glu
    act_limit: float = 7.0,
    held: tuple = (),  # (first, count): the experts this chip holds
    valid: Optional[jax.Array] = None,  # [B, T] bool: real tokens (aux only)
    zero: int = 0,  # identity experts among the router's outputs
) -> tuple[jax.Array, dict]:
    """Sparse SwiGLU FFN → (output [B,T,H], aux losses).

    ``sigmoid_input`` (Llama4): the sigmoid gate multiplies the token
    *before* the expert FFN (scaling through the nonlinearity) and the
    return combine is unweighted; a dense shared expert
    (``w_shared_gate/up/down`` in ``layer``) adds to every token.

    ``held`` (a chip's share of the layer, see :func:`router`): the
    expert leaves of ``layer`` are [count, ...], only those experts are
    computed, and the output is their partial sum plus the shared
    expert: nothing stands in for the absent ones. ``aux
    ["experts_read"]`` is the number of experts whose weights the call
    read (the distinct picked ones in the picked form, ``count`` in the
    capacity form), ``aux["experts_held"]`` is ``count``.

    Which form computes the experts: :func:`reads_picked_experts`.

    ``zero`` (see :func:`router`): a pick of an identity expert adds
    its gate times ``x``, at no weights and no capacity slot.
    """
    b, t, h = x.shape
    cap = expert_capacity(t, n_experts, experts_per_token, capacity_factor)
    routing = dict(
        renorm=renorm, sigmoid=sigmoid_input, score=score, groups=groups,
        bias=layer.get("router_bias"), routed_scale=routed_scale,
        pre_bias=layer.get("b_router"), topk_softmax=topk_softmax,
    )
    if reads_picked_experts(
        layer, b, t, n_experts, experts_per_token, capacity_factor, rules,
        sigmoid_input=sigmoid_input, act=act,
    ):
        logits, probs, expert_idx, gate_vals = select(
            x, layer["w_router"], experts_per_token, **routing
        )
        aux = _routing_aux(
            logits, probs, expert_idx, gate_vals, held, valid, zero,
            groups=groups, score=score, bias=routing["bias"],
        )
        with jax.named_scope("dtpu.moe_held" if held else "dtpu.moe"):
            out, read = _picked_experts(
                x, layer, expert_idx, gate_vals,
                held or (0, logits.shape[-1] - zero), valid,
            )
    else:
        out, aux = _every_expert(
            x, layer, n_experts, experts_per_token, cap, mesh, rules, routing,
            sigmoid_input, act, act_limit, held, valid, zero,
        )
        read = None  # the batched einsums stream the whole stack
    if held:
        aux["experts_held"] = jnp.asarray(held[1], jnp.int32)
        aux["experts_read"] = aux["experts_held"] if read is None else read
    if zero:
        with jax.named_scope("dtpu.moe_zero"):
            out = out + (
                aux["zero_gate"][..., None] * x.astype(jnp.float32)
            ).astype(x.dtype)
    if "w_shared_gate" in layer or "w_shared_gate_q" in layer:
        # Llama4/DeepSeek dense shared expert: plain 2D matmuls, so
        # llama._proj resolves the int8 form (and any LoRA bypass)
        from dstack_tpu.models.llama import _proj

        sg = _proj(layer, "w_shared_gate", x, "bth,hf->btf", "bth,hr->btr", "btr,rf->btf")
        su = _proj(layer, "w_shared_up", x, "bth,hf->btf", "bth,hr->btr", "btr,rf->btf")
        out = out + _proj(
            layer, "w_shared_down", jax.nn.silu(sg) * su,
            "btf,fh->bth", "btf,fr->btr", "btr,rh->bth",
        )
    if rules is not None:
        out = constrain(out, rules, "batch", "seq", None, mesh=mesh)
    return out, aux


def moe_mlp_reference(
    x: jax.Array,
    layer: dict,
    n_experts: int,
    experts_per_token: int,
    renorm: bool = False,
) -> jax.Array:
    """Dense-everything reference (no capacity, no dispatch): every token
    runs every expert, output = Σ top-k gate_e · FFN_e(x). For tests."""
    logits = jnp.einsum("bth,he->bte", x, layer["w_router"].astype(x.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, experts_per_token)
    if renorm:
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    gates = jnp.zeros_like(probs)
    for j in range(experts_per_token):
        gates = gates + jax.nn.one_hot(
            expert_idx[..., j], n_experts, dtype=jnp.float32
        ) * gate_vals[..., j, None]
    g = jnp.einsum("bth,ehf->ebtf", x, layer["w_gate"])
    u = jnp.einsum("bth,ehf->ebtf", x, layer["w_up"])
    y = jnp.einsum("ebtf,efh->ebth", jax.nn.silu(g) * u, layer["w_down"])
    return jnp.einsum("bte,ebth->bth", gates.astype(x.dtype), y)
