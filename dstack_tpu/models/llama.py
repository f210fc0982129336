"""Llama-family transformer, TPU-first.

Pure-functional JAX: parameters are plain pytrees with a parallel
*logical-axis spec tree* (see ``dstack_tpu.parallel.sharding``), layers
are stacked on a leading ``layers`` dim and executed with ``lax.scan``
(single trace/compile of the layer body — XLA-friendly, fast compiles
even at 80 layers), matmuls in bf16 on the MXU with f32 accumulation,
rematerialization on the layer boundary.

This is the compute-plane flagship used by the serve engine, the
fine-tune entry point and ``__graft_entry__.py``; the orchestrator
treats it as user code (the
reference ships torch examples instead — examples/fine-tuning).
"""

import dataclasses
import functools
import math
from dataclasses import dataclass
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from dstack_tpu.ops.attention import attention
from dstack_tpu.parallel.ring_attention import ring_attention
from dstack_tpu.parallel.sharding import (
    ShardingRules,
    constrain,
    default_rules,
    kernel_shard,
)


#: a layer kind → the stack of ``params`` its layers past the prelude are in
STACK_OF = {
    "full": "layers", "window": "window_layers", "linear": "linear_layers",
    "conv": "conv_layers", "mamba": "mamba_layers", "gmu": "gmu_layers",
    "cross": "cross_layers",
}

#: the kinds whose layers mix their tokens through a module of
#: :func:`mixer_of` and hold a slot's past whole (a model has one at most)
STATE_KINDS = ("linear", "conv", "mamba")


def mixer_of(kind: str):
    """The module of a kind of layer that mixes its tokens without
    attention (``leaf_shapes``, ``n_params``, ``mix``, ``zeros``): a
    slot's past on such a layer is held whole, not by position."""
    from dstack_tpu.models import kda, mamba, shortconv

    return {"linear": kda, "conv": shortconv, "mamba": mamba}[kind]


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 14336
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # Mixture-of-Experts (models/moe.py): n_experts == 0 → dense MLP
    n_experts: int = 0
    experts_per_token: int = 2
    capacity_factor: float = 1.25
    router_balance_coef: float = 0.01
    router_z_coef: float = 1e-3
    router_renorm: bool = False  # Mixtral: renormalize top-k gates
    # --- model-family deltas (all default to Llama behavior) ---
    qkv_bias: bool = False  # Qwen2: bias on q/k/v projections
    qk_norm: bool = False  # Qwen3: RMSNorm over head_dim on q/k pre-rope
    sliding_window: int = 0  # Mistral/Gemma2: 0 = full attention
    # every `sliding_pattern` layers the LAST is global, the rest use the
    # sliding window (Gemma2: pattern=2 → layers 0,2,… sliding); 0/1 =
    # uniform window on all layers
    sliding_pattern: int = 0
    hidden_act: str = "silu"  # "silu" | "gelu_tanh" (Gemma)
    norm_offset: bool = False  # Gemma RMSNorm scales by (1 + w)
    embed_scale: bool = False  # Gemma multiplies embeddings by sqrt(H)
    post_norms: bool = False  # Gemma2 sandwich norms around attn/mlp
    attn_softcap: float = 0.0  # Gemma2 tanh soft-cap on attention scores
    logit_softcap: float = 0.0  # Gemma2 tanh soft-cap on final logits
    attn_scale: Optional[float] = None  # override 1/sqrt(head_dim)
    # Llama-3.1+ rope scaling: (factor, low_freq_factor,
    # high_freq_factor, original_max_position_embeddings), or the tagged
    # forms ("llama3", factor, low, high, orig) / ("linear", factor)
    # (Gemma3 global layers use linear position interpolation); None =
    # plain rope_theta frequencies
    rope_scaling: Optional[tuple] = None
    # Gemma3 dual rope: sliding-window layers use this unscaled theta
    # while global layers use rope_theta (+ rope_scaling). 0 = single
    # rope for all layers.
    rope_local_theta: float = 0.0
    # --- Llama4 deltas ---
    # every `nope_pattern`-th layer skips rope entirely (NoPE long-
    # context layers; Llama4: 4). 0 = rope everywhere.
    nope_pattern: int = 0
    # rope rotates interleaved (even, odd) pairs as complex numbers
    # (Meta's original convention, kept by Llama4) instead of
    # rotate-half
    rope_interleaved: bool = False
    # weightless L2 norm (x/rms(x), f32) on q/k AFTER rope, rope
    # layers only
    qk_l2_norm: bool = False
    # rope layers attend within `attention_chunk_size`-token chunks
    # (blockwise-local, NOT a sliding window); NoPE layers stay global.
    # 0 = off.
    attention_chunk_size: int = 0
    # NoPE-layer query temperature tuning:
    # q *= 1 + attn_temp_scale * log1p(floor((pos+1)/attn_temp_floor))
    attn_temp_scale: float = 0.0
    attn_temp_floor: float = 8192.0
    # Llama4 MoE: gates are sigmoid(top-k logit) applied to the expert
    # INPUT (not the output), plus a dense shared expert on every MoE
    # layer
    router_sigmoid_input: bool = False
    moe_shared_expert: bool = False
    # sequence-parallel strategy on sp>1 meshes: "ring" (KV rotation,
    # any head count, lowest memory) or "ulysses" (head⇄seq all_to_all,
    # needs n_heads % sp == 0, keeps the flash kernel for windows)
    seq_parallel: str = "ring"
    # GLM: rope rotates only the first head_dim*partial_rotary dims
    # (interleaved convention — GLM sets rope_interleaved too); the
    # rest pass through unrotated. 1.0 = full-width rope.
    partial_rotary: float = 1.0
    # OLMo-2: no pre-norms — sublayer OUTPUTS are normed instead
    # (pre_norm=False implies post_norms=True; attn_norm/mlp_norm
    # leave the param tree entirely)
    pre_norm: bool = True
    # OLMo-2: q/k RMSNorm over the FULL projection width (all heads
    # jointly, before the head reshape) — distinct from qk_norm's
    # per-head-dim norm (Qwen3/Gemma3)
    qk_norm_flat: bool = False
    # --- Cohere (Command-R) deltas ---
    # "layernorm": mean-centered, weight-only LayerNorm (Cohere);
    # "layernorm1p": mean-centered with (1 + w) scale AND bias, stored
    # STACKED as [..., 2, H] = (scale-1, bias) rows (Nemotron); "rms"
    # is everyone else
    norm_type: str = "rms"
    # parallel residual: attention and MLP both read the SAME layer
    # input and their outputs add jointly (x + attn(n(x)) + mlp(n(x)));
    # the converter aliases Cohere's single input_layernorm into both
    # attn_norm and mlp_norm slots
    parallel_block: bool = False
    # multiplier on the final logits (Cohere logit_scale; Granite uses
    # 1/logits_scaling); 0 = off
    logit_scale: float = 0.0
    # Nemotron: gateless MLP — down(act(up(x))), no gate matrix
    mlp_gateless: bool = False
    # StarCoder2: biases on the o projection and the gateless MLP
    # (bo / b_up / b_down; q/k/v biases ride qkv_bias)
    proj_bias: bool = False
    # --- IBM Granite deltas (scalar multipliers on the llama skeleton;
    # attention_multiplier maps onto attn_scale) ---
    embed_multiplier: float = 0.0  # scales embeddings (0 = off)
    residual_multiplier: float = 0.0  # scales sublayer outputs (0 = off)
    # --- DeepSeek MLA (multi-head latent attention) deltas ---
    # kv_lora_rank > 0 enables MLA: k/v decode from a shared low-rank
    # latent (kv_a_proj → rmsnorm → kv_b_proj), q/k heads split into a
    # rope-free "nope" part and a single-head-shared rope part, and v
    # has its own head dim. head_dim/n_kv_heads are unused under MLA
    # (reference for the math: HF deepseek_v2 modeling, which this
    # matches logit-exactly in tests/compute/test_hf_parity.py).
    q_lora_rank: int = 0  # 0 = direct wq projection (V2-Lite)
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # --- DeepSeek MoE deltas (models/moe.py) ---
    router_score: str = "softmax"  # "softmax" (V2) | "sigmoid" (V3)
    router_bias: bool = False  # V3 e_score_correction_bias (selection only)
    # (n_group, topk_group): group-limited top-k — experts partition
    # into n_group groups, only the best topk_group groups are eligible
    # (group score: max member for softmax/V2, top-2 sum for sigmoid/V3)
    router_groups: tuple = ()
    routed_scale: float = 1.0  # multiplier on routed gates
    # --- gpt-oss deltas ---
    # learned per-head attention-sink logits: an always-present softmax
    # column that absorbs probability mass (params["layers"]["sinks"])
    attn_sinks: bool = False
    # router is a LINEAR layer (logit bias b_router) and gates are
    # softmax over the top-k logits (select-then-normalize)
    router_topk_softmax: bool = False
    # biases on every expert matmul (b_gate/b_up_e [E,F], b_down_e [E,H])
    # and on the router
    moe_bias: bool = False
    # expert activation: "silu" (SwiGLU) | "oai_glu" (gpt-oss clamped
    # glu: (up+1) * gate * sigmoid(1.702 * gate), inputs clamped to
    # act_limit)
    moe_act: str = "silu"
    act_limit: float = 7.0
    # shared always-on expert FFN width (0 = intermediate_size); HF
    # deepseek folds n_shared_experts into ONE fused MLP of this width
    moe_shared_intermediate: int = 0
    # DeepSeek: the first k layers use a plain dense FFN (width
    # dense_intermediate) instead of MoE — they live in a separate
    # params["dense_layers"] stack scanned before the main layers
    first_k_dense: int = 0
    dense_intermediate: int = 0
    # --- layer groups: two attention shapes in one model ---
    # per-layer kind over all n_layers, "full" | "window" | "linear" |
    # "conv" (() = every layer full; the first_k_dense prelude layers
    # are all full, all linear or all conv). A
    # window layer is attention of ANOTHER shape (the swa_* sizes: of
    # an MLA model the latent's, of a grouped-query model the query
    # head count over the same KV heads), sees key j from query i iff
    # 0 <= i - j < sliding_window, rotates with rope_local_theta (no
    # scaling) over swa_partial_rotary of its head, and its weights are
    # a stack of their own, params["window_layers"] (full layers:
    # "dense_layers" + "layers"). :func:`layer_runs` is the one place
    # that turns this into the order the stacks are walked in.
    layer_types: tuple = ()
    swa_n_heads: int = 0
    swa_partial_rotary: float = 0.0  # 0 = partial_rotary
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_v_head_dim: int = 0
    # head-wise output gate (arXiv:2505.06708): head a's attention
    # output scales by sigmoid(h · w_og)[a] before wo
    attn_gate: bool = False
    # the normed q / kv latents scale by sqrt(hidden / rank)
    mla_lora_rescale: bool = False
    # learned sparse indexer on the full layers (DeepSeek-V3.2): query i
    # attends to the index_topk causal keys with the largest
    # sum_h w_h(i) relu(q_I,h(i) . k_I(j)); 0 = off
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # this chip's share of every expert layer, (first, count): the
    # router stays n_experts wide, only these experts' weights exist and
    # are computed, and the layer passes their partial sum on (what
    # expert parallelism holds on one chip); () = all of them
    experts_held: tuple = ()
    # --- expert layers with a shortcut across several sublayers ---
    # a layer is `sublayers` pairs of (attention, dense FFN of width
    # dense_intermediate), each behind its own residual, with weights
    # and a cache row of its own: sub-trees "sub0", "sub1", ... of the
    # layer stack, each a plain dense layer's leaves [L, ...] (stacked
    # [L, sublayers, ...] the layer scan's slice has a consumer a
    # sublayer, and the TPU compiler copies every such weight out a
    # layer: PERF.md §6, PR 37). The layer's experts read the
    # normed hidden after the FIRST attention, and their sum joins the
    # residual after the LAST dense FFN: the branch runs beside the
    # sublayers between (arXiv:2509.01322). 1 = attention, then one MLP
    sublayers: int = 1
    # router outputs past n_experts that stand for no weights: a pick of
    # one returns the expert's input times its gate ("zero-computation"
    # identity experts); the router is n_experts + zero_experts wide
    zero_experts: int = 0
    # --- linear-attention layers (models/kda.py) ---
    # a layer of kind "linear" in layer_types mixes its tokens through a
    # gated delta rule with a decay a channel (KDA) behind a causal
    # depthwise convolution: n_heads heads of linear_head_dim, no rope,
    # no keys or values kept: a slot's past is a float32 state
    # [n_heads, D, D] and the convolution's last linear_conv - 1 rows.
    # Its weights are a stack of their own, params["linear_layers"]
    # (a first_k_dense prelude of linear layers keeps "dense_layers").
    # The log-decay is linear_gate_floor * sigmoid(.), in (floor, 0)
    linear_head_dim: int = 0
    linear_conv: int = 4
    linear_gate_floor: float = -5.0
    # --- gated short-convolution layers (models/shortconv.py) ---
    # a layer of kind "conv" in layer_types mixes its tokens through a
    # causal depthwise convolution of conv_taps taps over the hidden
    # itself, between two elementwise gates (LFM2), beside grouped-query
    # attention: no keys, values or state kept, a slot's past is the
    # convolution's last conv_taps - 1 rows of hidden_size. Its weights
    # are a stack of their own, params["conv_layers"] (a first_k_dense
    # prelude of conv layers keeps "dense_layers")
    conv_taps: int = 3
    # --- state-space layers (models/mamba.py) and the layers that keep
    # nothing (a decoder-hybrid-decoder stack, arXiv:2507.06607) ---
    # a layer of kind "mamba" mixes its tokens through a diagonal
    # selective recurrence (Mamba-1) over ssm_expand * hidden channels,
    # beside grouped-query attention: a slot's past is a float32 state
    # [d_inner, ssm_state] and the convolution's last ssm_conv - 1 rows
    # of d_inner; params["mamba_layers"]. Its scan output, before the
    # gate, is what the "gmu" layers after it read at the same position
    # (a gated memory unit: no state, no rows; params["gmu_layers"]).
    # A layer of kind "cross" has queries alone and attends over the
    # rows of the model's ONE full layer, which precedes it (no rows of
    # its own; params["cross_layers"])
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: int = 0  # 0 = ceil(hidden / 16)
    # differential attention (arXiv:2410.05258) in every attending layer:
    # query heads (2p, 2p+1) and KV heads (2g, 2g+1), g = p // 2, are a
    # pair; softmax(q1 k1) [v1|v2] - lambda softmax(q2 k2) [v1|v2],
    # RMSNormed over the 2 * head_dim of the pair under a weight stored
    # as (w - 1), times (1 - lambda_init(layer)). No rotary: a model of
    # recurrences that carry position sets partial_rotary = 0
    diff_attn: bool = False
    # a bias on the attention's output projection alone (``bo``), none
    # on the MLP (``proj_bias`` ties the two)
    wo_bias: bool = False

    def __post_init__(self):
        kinds = set(self.layer_types)
        prelude = set(self.layer_types[: self.first_k_dense])
        if self.layer_types and (
            len(self.layer_types) != self.n_layers
            or not kinds <= set(STACK_OF)
            or prelude not in (set(), {"full"}, {"linear"}, {"conv"}, {"mamba"})
            or self.sliding_pattern or self.nope_pattern
        ):
            raise ValueError(
                "layer_types: one of 'full' | 'window' | 'linear' | 'conv' | "
                "'mamba' | 'gmu' | 'cross' a layer (in place of "
                "sliding_pattern / nope_pattern), the first_k_dense prelude "
                "of one kind: 'full', 'linear', 'conv' or 'mamba'"
            )
        if len(kinds & set(STATE_KINDS)) > 1:
            raise ValueError(
                "layer_types: one kind of layer that holds a slot's past "
                "whole ('linear' | 'conv' | 'mamba') a model"
            )
        if "linear" in kinds and not (
            self.mla and self.linear_head_dim and self.linear_conv > 1
            and self.sublayers == 1 and self.pre_norm
            and not self.post_norms and not self.parallel_block
            # a block of the chunkwise form holds its decay in float32
            and -self.linear_gate_floor * 16 < 88
        ):
            raise ValueError(
                "linear layers: beside latent attention, with "
                "linear_head_dim, plainly pre-normed, a gate floor over -5.5"
            )
        if "conv" in kinds and not (
            not self.mla and self.conv_taps > 1
            and self.sublayers == 1 and self.pre_norm
            and not self.post_norms and not self.parallel_block
        ):
            raise ValueError(
                "conv layers: beside grouped-query attention, plainly "
                "pre-normed, one sublayer, two taps or more"
            )
        if "mamba" in kinds and not (
            not self.mla and self.ssm_conv > 1 and self.ssm_state > 0
            and self.sublayers == 1 and self.pre_norm
            and not self.post_norms and not self.parallel_block
        ):
            raise ValueError(
                "mamba layers: beside grouped-query attention, plainly "
                "pre-normed, one sublayer, two taps or more"
            )
        types = self.layer_types
        if "gmu" in kinds and "mamba" not in types[: types.index("gmu")]:
            raise ValueError("a gmu layer reads the scan of a mamba layer before it")
        if "cross" in kinds and (
            self.mla or not self.diff_attn or types.count("full") != 1
            or types.index("full") > types.index("cross")
        ):
            raise ValueError(
                "cross layers read the rows of the model's one full "
                "(differential grouped-query) layer, which precedes them"
            )
        if self.diff_attn and (
            self.mla or self.n_heads % 2 or self.n_kv_heads % 2
            or (self.n_heads // 2) % (self.n_kv_heads // 2)
            or self.rope_dim or self.qk_norm or self.qk_norm_flat
            or self.attn_gate or self.attn_sinks or self.attn_softcap
        ):
            raise ValueError(
                "diff_attn: grouped-query heads in pairs, no rotary "
                "(partial_rotary = 0), no q/k norm, gate, sinks or softcap"
            )
        if self.experts_held and self.router_groups and (
            self.n_experts % self.router_groups[0]
            or any(
                n % (self.n_experts // self.router_groups[0])
                for n in self.experts_held
            )
        ):
            raise ValueError(
                "experts_held: a whole number of the router's groups "
                "(a chip of an expert-parallel layer holds whole groups)"
            )
        if "window" in kinds and not (
            self.sliding_window and self.swa_n_heads
            and (self.swa_kv_lora_rank or not self.mla)
            and self.swa_n_heads % (1 if self.mla else self.n_kv_heads) == 0
        ):
            raise ValueError(
                "window layers need sliding_window and the swa_* sizes"
            )
        if self.sublayers > 1 and not (
            self.mla and self.n_experts and self.dense_intermediate
            and self.pre_norm and not self.layer_types
            and not self.first_k_dense and not self.parallel_block
            and not self.post_norms and not self.proj_bias
            and not self.moe_shared_expert and not self.moe_bias
        ):
            raise ValueError(
                "sublayers > 1: latent attention, experts and "
                "dense_intermediate, one kind of plainly pre-normed "
                "layer without biases or a shared expert"
            )
        if self.zero_experts and not self.n_experts:
            raise ValueError("zero_experts are outputs of an expert router")
        if self.qk_norm and self.qk_norm_flat:
            raise ValueError(
                "qk_norm (per-head, Qwen3) and qk_norm_flat (full "
                "width, OLMo-2) are mutually exclusive"
            )
        if not self.pre_norm and not self.post_norms:
            raise ValueError("pre_norm=False requires post_norms=True")

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def prelude_kind(self) -> str:
        """The kind of the ``first_k_dense`` prelude's layers."""
        return self.layer_types[0] if self.layer_types else "full"

    def n_kind(self, kind: str, prelude: bool = True) -> int:
        """Layers of ``kind`` (``prelude``: the prelude's counted)."""
        if not self.layer_types:
            n = self.n_layers if kind == "full" else 0
            return n - (0 if prelude or kind != "full" else self.first_k_dense)
        return self.layer_types[0 if prelude else self.first_k_dense:].count(kind)

    @property
    def window_config(self) -> "LlamaConfig":
        """The window layers' attention shape as a config of its own:
        everything that reads an attention shape off a config
        (projections, absorbed forms, caches) takes this one for a
        window layer."""
        if not self.mla:
            return dataclasses.replace(
                self, n_heads=self.swa_n_heads,
                partial_rotary=self.swa_partial_rotary or self.partial_rotary,
                rope_theta=self.rope_local_theta or self.rope_theta,
                rope_scaling=None if self.rope_local_theta else self.rope_scaling,
                layer_types=(),
            )
        return dataclasses.replace(
            self, n_heads=self.swa_n_heads,
            q_lora_rank=self.swa_q_lora_rank,
            kv_lora_rank=self.swa_kv_lora_rank,
            qk_nope_head_dim=self.swa_qk_nope_head_dim,
            v_head_dim=self.swa_v_head_dim,
            index_topk=0, layer_types=(),
        )

    @property
    def ssm_inner(self) -> int:
        """Channels of a mamba layer's recurrence (``d_inner``)."""
        return self.ssm_expand * self.hidden_size

    @property
    def ssm_rank(self) -> int:
        """Width of a mamba layer's step-size latent (``dt_rank``)."""
        return self.ssm_dt_rank or -(-self.hidden_size // 16)

    @property
    def attend_config(self) -> "LlamaConfig":
        """The shape the attention itself runs at and the cache holds.
        Differential attention runs as plain grouped-query attention
        over KV PAIRS: keys and values of heads (2g, 2g+1) side by side
        are one head of twice the width (a reshape of what the
        projection gives), a query head zero-padded into its key's half
        scores q1 . k1 or q2 . k2 alone against it, and its value is
        [v1 | v2]: K and V are each read once, at a width that fills
        the lanes. Any other model: itself."""
        if not self.diff_attn:
            return self
        return dataclasses.replace(
            self, head_dim=2 * self.head_dim, n_kv_heads=self.n_kv_heads // 2,
            attn_scale=self.attention_scale, diff_attn=False, layer_types=(),
        )

    @property
    def n_experts_held(self) -> int:
        return self.experts_held[1] if self.experts_held else self.n_experts

    @property
    def qk_head_dim(self) -> int:
        """Per-head q/k width (nope + rope parts under MLA)."""
        if self.mla:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.head_dim

    @property
    def rope_dim(self) -> int:
        """Width the rotary embedding acts on (the pe slice under MLA,
        the first partial_rotary fraction for GLM)."""
        if self.mla:
            return self.qk_rope_head_dim
        return int(self.head_dim * self.partial_rotary)

    @property
    def rope_dim_local(self) -> int:
        """:attr:`rope_dim` of the layers that rotate with the local
        rope (``swa_partial_rotary`` of a grouped-query head)."""
        if self.mla or not self.swa_partial_rotary:
            return self.rope_dim
        return int(self.head_dim * self.swa_partial_rotary)

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.qk_head_dim

    @property
    def o_dim(self) -> int:
        """Attention output width entering wo (v heads under MLA)."""
        return self.n_heads * (self.v_head_dim if self.mla else self.head_dim)

    @property
    def attention_scale(self) -> float:
        return (
            self.attn_scale if self.attn_scale is not None
            else self.qk_head_dim**-0.5
        )

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    def _attn_params_per_layer(self) -> int:
        h = self.hidden_size
        if self.mla:
            q = (
                h * self.q_lora_rank + self.q_lora_rank
                + self.q_lora_rank * self.q_dim
                if self.q_lora_rank else h * self.q_dim
            )
            kv = (
                h * (self.kv_lora_rank + self.qk_rope_head_dim)
                + self.kv_lora_rank
                + self.kv_lora_rank
                * self.n_heads * (self.qk_nope_head_dim + self.v_head_dim)
            )
            extra = h * self.n_heads if self.attn_gate else 0
            if self.index_topk:
                extra += (
                    (self.q_lora_rank or h)
                    * self.index_n_heads * self.index_head_dim
                    + h * self.index_head_dim + self.index_head_dim
                    + h * self.index_n_heads
                )
            return q + kv + self.o_dim * h + extra
        qk_norm = (
            self.q_dim + self.kv_dim
            if self.qk_norm_flat or self.qk_norm and self.norm_type == "layernorm"
            else 2 * self.head_dim if self.qk_norm else 0
        )
        return (
            h * self.q_dim + 2 * h * self.kv_dim + self.q_dim * h
            + (self.q_dim + 2 * self.kv_dim if self.qkv_bias else 0)
            + (h if self.proj_bias or self.wo_bias else 0)  # bo
            + (h * self.n_heads if self.attn_gate else 0)
            + qk_norm
            + (6 * self.head_dim if self.diff_attn else 0)  # lambdas, sub-norm
        )

    def _shared_expert_params(self) -> int:
        if not (self.n_experts and self.moe_shared_expert):
            return 0
        inter = self.moe_shared_intermediate or self.intermediate_size
        return 3 * self.hidden_size * inter

    def _attn_params_total(self) -> int:
        """Attention parameters over all layers (a window layer has
        its own shape)."""
        n_win, h_in = self.layer_types.count("window"), self.hidden_size
        total = self.n_kind("full") * self.sublayers * self._attn_params_per_layer()
        if n_win:
            total += n_win * self.window_config._attn_params_per_layer()
        for kind in STATE_KINDS:
            if kind in self.layer_types:
                total += self.n_kind(kind) * mixer_of(kind).n_params(self)
        # a gmu layer's two projections; a cross layer's attention
        # without its keys and values
        total += self.n_kind("gmu") * 2 * h_in * self.ssm_inner
        total += self.n_kind("cross") * (
            self._attn_params_per_layer()
            - 2 * (h_in + bool(self.qkv_bias)) * self.kv_dim
        )
        return total

    def _param_count(self, experts: int, small: bool = True) -> int:
        """Parameters with ``experts`` routed experts counted a layer
        (``small``: the experts' biases and the sinks too)."""
        e, h = self.vocab_size * self.hidden_size, self.hidden_size
        pre = (1 if self.parallel_block else 2) if self.pre_norm else 0
        # stacked (scale, bias) norm types carry 2H per norm
        nw = 2 * h if self.norm_type in ("layernorm1p", "layernorm_bias") else h
        extras = pre * nw + (2 * nw if self.post_norms else 0)
        mats = 2 if self.mlp_gateless else 3  # StarCoder2/Nemotron
        mlp_bias = (
            self.intermediate_size + h
            if self.proj_bias and not self.n_experts else 0
        )
        moe_bias = (
            self.n_experts * (1 + 2 * self.intermediate_size + h)
            if self.moe_bias and small else 0
        )
        sink = self.n_heads if self.attn_sinks and small else 0
        moe_layers = self.n_layers - self.first_k_dense
        router = self.n_experts + self.zero_experts  # its width
        per_moe = (
            self.sublayers * extras
            + max(1, experts) * mats * h * self.intermediate_size
            + mlp_bias
            + self._shared_expert_params()
            + (h * router if self.n_experts else 0)
            + (router if self.router_bias else 0)
            + moe_bias + sink
        )
        if self.sublayers > 1:  # a dense FFN a sublayer beside the experts
            per_moe += self.sublayers * mats * h * self.dense_intermediate
        per_dense = (
            extras
            + mats * h * (self.dense_intermediate or self.intermediate_size)
        )
        out = 0 if self.tie_embeddings else e
        return (
            e + self._attn_params_total() + moe_layers * per_moe
            + self.first_k_dense * per_dense + nw + out
        )

    def num_params(self) -> int:
        """Parameters held (with ``experts_held``: this chip's share)."""
        return self._param_count(self.n_experts_held)

    def num_active_params(self) -> int:
        """Parameters touched per token: for MoE, only the
        ``experts_per_token`` routed experts' FFNs (plus the always-on
        shared expert) count — MFU/FLOPs estimates must use this, not
        :meth:`num_params`."""
        if not self.n_experts:
            return self.num_params()
        return self._param_count(self.experts_per_token, small=False)


LLAMA_3_8B = LlamaConfig()
LLAMA_3_70B = LlamaConfig(
    hidden_size=8192, n_layers=80, n_heads=64, n_kv_heads=8,
    intermediate_size=28672,
)
LLAMA_32_1B = LlamaConfig(
    hidden_size=2048, n_layers=16, n_heads=32, n_kv_heads=8, head_dim=64,
    intermediate_size=8192, tie_embeddings=True,
)
LLAMA_32_3B = LlamaConfig(
    hidden_size=3072, n_layers=28, n_heads=24, n_kv_heads=8,
    intermediate_size=8192, tie_embeddings=True,
)
LLAMA_TINY = LlamaConfig(  # for tests / virtual meshes
    vocab_size=512, hidden_size=128, n_layers=2, n_heads=4, n_kv_heads=2,
    head_dim=32, intermediate_size=256, max_seq_len=256, dtype=jnp.float32,
    remat=False,
)
LLAMA_TINY_64 = LlamaConfig(  # head_dim-64 tiny: pallas-kernel-eligible
    vocab_size=512, hidden_size=128, n_layers=2, n_heads=2, n_kv_heads=1,
    head_dim=64, intermediate_size=256, max_seq_len=256, dtype=jnp.float32,
    remat=False,
)
MIXTRAL_8X7B = LlamaConfig(
    vocab_size=32000, hidden_size=4096, n_layers=32, n_heads=32, n_kv_heads=8,
    intermediate_size=14336, rope_theta=1e6, n_experts=8, experts_per_token=2,
)
MOE_TINY = LlamaConfig(  # for tests / virtual meshes
    vocab_size=512, hidden_size=128, n_layers=2, n_heads=4, n_kv_heads=2,
    head_dim=32, intermediate_size=256, max_seq_len=256, dtype=jnp.float32,
    remat=False, n_experts=4, experts_per_token=2, capacity_factor=2.0,
)
# Model families beyond Llama: the architecture deltas are config flags
# (models/convert_hf.py maps HF checkpoints onto them)
QWEN3_8B = LlamaConfig(
    vocab_size=151936, hidden_size=4096, n_layers=36, n_heads=32,
    n_kv_heads=8, head_dim=128, intermediate_size=12288, rope_theta=1e6,
    norm_eps=1e-6, max_seq_len=32768, qk_norm=True,
)
QWEN25_7B = LlamaConfig(
    vocab_size=152064, hidden_size=3584, n_layers=28, n_heads=28,
    n_kv_heads=4, head_dim=128, intermediate_size=18944, rope_theta=1e6,
    norm_eps=1e-6, max_seq_len=32768, qkv_bias=True,
)
QWEN3_30B_A3B = LlamaConfig(  # sparse MoE: 30B total, ~3B active
    vocab_size=151936, hidden_size=2048, n_layers=48, n_heads=32,
    n_kv_heads=4, head_dim=128, intermediate_size=768, rope_theta=1e6,
    norm_eps=1e-6, max_seq_len=32768, qk_norm=True,
    n_experts=128, experts_per_token=8, router_renorm=True,
)
MISTRAL_7B = LlamaConfig(
    vocab_size=32000, hidden_size=4096, n_layers=32, n_heads=32,
    n_kv_heads=8, head_dim=128, intermediate_size=14336, rope_theta=10000.0,
    sliding_window=4096,
)
GEMMA_2B = LlamaConfig(
    vocab_size=256000, hidden_size=2048, n_layers=18, n_heads=8,
    n_kv_heads=1, head_dim=256, intermediate_size=16384, rope_theta=10000.0,
    norm_eps=1e-6, tie_embeddings=True, hidden_act="gelu_tanh",
    norm_offset=True, embed_scale=True,
)
GEMMA2_2B = LlamaConfig(
    vocab_size=256000, hidden_size=2304, n_layers=26, n_heads=8,
    n_kv_heads=4, head_dim=256, intermediate_size=9216, rope_theta=10000.0,
    norm_eps=1e-6, tie_embeddings=True, hidden_act="gelu_tanh",
    norm_offset=True, embed_scale=True, post_norms=True,
    sliding_window=4096, sliding_pattern=2,
    attn_softcap=50.0, logit_softcap=30.0, attn_scale=256.0**-0.5,
)
# Gemma3: 5 sliding layers per global one, dual rope theta (local 10k
# on sliding layers, 1M + linear interpolation on global), qk-norm,
# no softcaps (google/gemma-3-*-it config.json)
GEMMA3_1B = LlamaConfig(
    vocab_size=262144, hidden_size=1152, n_layers=26, n_heads=4,
    n_kv_heads=1, head_dim=256, intermediate_size=6912, rope_theta=1e6,
    norm_eps=1e-6, max_seq_len=32768, tie_embeddings=True,
    hidden_act="gelu_tanh", norm_offset=True, embed_scale=True,
    post_norms=True, qk_norm=True, sliding_window=512, sliding_pattern=6,
    rope_local_theta=10000.0, attn_scale=256.0**-0.5,
)
LLAMA4_SCOUT = LlamaConfig(  # meta-llama/Llama-4-Scout-17B-16E text tower
    vocab_size=202048, hidden_size=5120, n_layers=48, n_heads=40,
    n_kv_heads=8, head_dim=128, intermediate_size=8192, rope_theta=500000.0,
    norm_eps=1e-5, max_seq_len=262144,
    rope_interleaved=True, nope_pattern=4, attention_chunk_size=8192,
    qk_l2_norm=True, attn_temp_scale=0.1, attn_temp_floor=8192.0,
    n_experts=16, experts_per_token=1, router_sigmoid_input=True,
    moe_shared_expert=True,
)
GEMMA3_4B = LlamaConfig(  # text tower of google/gemma-3-4b
    vocab_size=262208, hidden_size=2560, n_layers=34, n_heads=8,
    n_kv_heads=4, head_dim=256, intermediate_size=10240, rope_theta=1e6,
    norm_eps=1e-6, max_seq_len=131072, tie_embeddings=True,
    hidden_act="gelu_tanh", norm_offset=True, embed_scale=True,
    post_norms=True, qk_norm=True, sliding_window=1024, sliding_pattern=6,
    rope_local_theta=10000.0, rope_scaling=("linear", 8.0),
    attn_scale=256.0**-0.5,
)

STARCODER2_7B = LlamaConfig(  # bigcode/starcoder2-7b
    vocab_size=49152, hidden_size=4608, n_layers=32, n_heads=36,
    n_kv_heads=4, head_dim=128, intermediate_size=18432,
    rope_theta=1000000.0, norm_eps=1e-5, max_seq_len=16384,
    tie_embeddings=True, norm_type="layernorm_bias", mlp_gateless=True,
    qkv_bias=True, proj_bias=True, hidden_act="gelu_tanh",
    sliding_window=4096,
)
MINITRON_4B = LlamaConfig(  # nvidia/Minitron-4B-Base (nemotron)
    vocab_size=256000, hidden_size=3072, n_layers=32, n_heads=24,
    n_kv_heads=8, head_dim=128, intermediate_size=9216,
    rope_theta=10000.0, norm_eps=1e-5, max_seq_len=4096,
    norm_type="layernorm1p", mlp_gateless=True, partial_rotary=0.5,
    hidden_act="relu2",
)
COMMAND_R_35B = LlamaConfig(  # CohereForAI/c4ai-command-r-v01
    vocab_size=256000, hidden_size=8192, n_layers=40, n_heads=64,
    n_kv_heads=64, head_dim=128, intermediate_size=22528,
    rope_theta=8000000.0, norm_eps=1e-5, max_seq_len=131072,
    tie_embeddings=True, norm_type="layernorm", parallel_block=True,
    rope_interleaved=True, logit_scale=0.0625,
)
OLMO2_7B = LlamaConfig(  # allenai/OLMo-2-1124-7B
    vocab_size=100352, hidden_size=4096, n_layers=32, n_heads=32,
    n_kv_heads=32, head_dim=128, intermediate_size=11008,
    rope_theta=500000.0, norm_eps=1e-6, max_seq_len=4096,
    pre_norm=False, post_norms=True, qk_norm_flat=True,
)
GLM_4_9B = LlamaConfig(  # THUDM/GLM-4-9B-0414 (glm4)
    vocab_size=151552, hidden_size=4096, n_layers=40, n_heads=32,
    n_kv_heads=2, head_dim=128, intermediate_size=13696,
    rope_theta=10000.0, norm_eps=1.5625e-7, max_seq_len=131072,
    qkv_bias=True, rope_interleaved=True, partial_rotary=0.5,
    post_norms=True,
)
DEEPSEEK_V2_LITE = LlamaConfig(  # deepseek-ai/DeepSeek-V2-Lite
    vocab_size=102400, hidden_size=2048, n_layers=27, n_heads=16,
    n_kv_heads=16, head_dim=64, intermediate_size=1408, rope_theta=10000.0,
    norm_eps=1e-6, max_seq_len=163840,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    rope_scaling=("yarn", 40.0, 32.0, 1.0, 4096.0, 1.0),
    n_experts=64, experts_per_token=6, moe_shared_expert=True,
    moe_shared_intermediate=2816,  # 2 shared experts × 1408
    first_k_dense=1, dense_intermediate=10944,
)
DEEPSEEK_V3 = LlamaConfig(  # deepseek-ai/DeepSeek-V3 (671B, 37B active)
    vocab_size=129280, hidden_size=7168, n_layers=61, n_heads=128,
    n_kv_heads=128, head_dim=64, intermediate_size=2048, rope_theta=10000.0,
    norm_eps=1e-6, max_seq_len=163840,
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128,
    rope_scaling=("yarn", 40.0, 32.0, 1.0, 4096.0, 1.0),
    # V3 under yarn multiplies the softmax scale by mscale(factor,
    # mscale_all_dim=1.0)^2 (HF DeepseekV3Attention; V2 does not)
    attn_scale=(192.0**-0.5) * (0.1 * math.log(40.0) + 1.0) ** 2,
    n_experts=256, experts_per_token=8, router_renorm=True,
    router_score="sigmoid", router_bias=True, router_groups=(8, 4),
    routed_scale=2.5, moe_shared_expert=True, moe_shared_intermediate=2048,
    first_k_dense=3, dense_intermediate=18432,
)
MLA_TINY = LlamaConfig(  # for tests / virtual meshes
    vocab_size=512, hidden_size=128, n_layers=3, n_heads=4, n_kv_heads=4,
    head_dim=16, intermediate_size=128, max_seq_len=256, dtype=jnp.float32,
    remat=False,
    q_lora_rank=48, kv_lora_rank=64, qk_nope_head_dim=32,
    qk_rope_head_dim=16, v_head_dim=24,
    n_experts=4, experts_per_token=2, capacity_factor=2.0,
    router_score="sigmoid", router_bias=True, router_groups=(2, 1),
    routed_scale=1.5, router_renorm=True,
    moe_shared_expert=True, moe_shared_intermediate=64,
    first_k_dense=1, dense_intermediate=192,
)
SCMOE_TINY = LlamaConfig(  # for tests: layers of two sublayers, the experts across
    vocab_size=512, hidden_size=128, n_layers=2, n_heads=4, n_kv_heads=4,
    head_dim=16, intermediate_size=64, max_seq_len=256, dtype=jnp.float32,
    remat=False,
    q_lora_rank=48, kv_lora_rank=64, qk_nope_head_dim=32,
    qk_rope_head_dim=16, v_head_dim=24, mla_lora_rescale=True,
    sublayers=2, dense_intermediate=192,
    n_experts=8, zero_experts=4, experts_per_token=3, capacity_factor=4.0,
    router_bias=True, routed_scale=6.0,
)
LINEAR_TINY = LlamaConfig(  # for tests: linear-attention layers beside latent ones
    vocab_size=512, hidden_size=128, n_layers=7, n_heads=4, n_kv_heads=4,
    head_dim=16, intermediate_size=64, max_seq_len=256, dtype=jnp.float32,
    remat=False, rope_interleaved=True,
    kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=24,
    attn_gate=True, linear_head_dim=16,
    layer_types=("linear",) + ("linear", "linear", "full") * 2,
    first_k_dense=1, dense_intermediate=192,
    n_experts=8, experts_per_token=2, capacity_factor=4.0,
    router_score="sigmoid", router_bias=True, router_groups=(4, 2),
    routed_scale=2.5, router_renorm=True, experts_held=(2, 2),
    moe_shared_expert=True, moe_shared_intermediate=64,
)
CONV_TINY = LlamaConfig(  # for tests: gated short-convolution layers beside grouped-query ones
    vocab_size=512, hidden_size=128, n_layers=12, n_heads=4, n_kv_heads=2,
    head_dim=32, intermediate_size=64, max_seq_len=256, dtype=jnp.float32,
    remat=False, qk_norm=True, tie_embeddings=True, norm_eps=1e-5,
    rope_theta=1e6, layer_types=("conv", "conv", "full", "conv") * 3,
    first_k_dense=2, dense_intermediate=192,
    n_experts=8, experts_per_token=2, capacity_factor=4.0,
    router_score="sigmoid", router_bias=True, router_renorm=True,
    experts_held=(2, 4),
)
SSM_TINY = LlamaConfig(  # for tests: state-space, differential window | full, gmu and cross layers
    vocab_size=512, hidden_size=128, n_layers=12, n_heads=8, n_kv_heads=4,
    head_dim=16, intermediate_size=192, max_seq_len=256, dtype=jnp.float32,
    remat=False, tie_embeddings=True, norm_eps=1e-5, norm_type="layernorm1p",
    partial_rotary=0.0, qkv_bias=True, wo_bias=True, diff_attn=True,
    sliding_window=24, swa_n_heads=8, ssm_dt_rank=8,
    layer_types=("mamba", "window") * 3 + ("mamba", "full") + ("gmu", "cross") * 2,
)

_GPT_OSS_COMMON = dict(
    vocab_size=201088, hidden_size=2880, n_heads=64, n_kv_heads=8,
    head_dim=64, intermediate_size=2880, rope_theta=150000.0,
    norm_eps=1e-5, max_seq_len=131072,
    rope_scaling=("yarn", 32.0, 32.0, 1.0, 4096.0, 1.3465735902799727, False),
    qkv_bias=True, proj_bias=True, attn_sinks=True,
    sliding_window=128, sliding_pattern=2,
    experts_per_token=4, router_topk_softmax=True, moe_bias=True,
    moe_act="oai_glu",
)
GPT_OSS_20B = LlamaConfig(  # openai/gpt-oss-20b (20.9B, 3.6B active)
    **_GPT_OSS_COMMON, n_layers=24, n_experts=32,
)
GPT_OSS_120B = LlamaConfig(  # openai/gpt-oss-120b (116.8B, 5.1B active)
    **_GPT_OSS_COMMON, n_layers=36, n_experts=128,
)
CONFIGS = {
    "llama-3-8b": LLAMA_3_8B,
    "llama-3-70b": LLAMA_3_70B,
    "llama-3.2-1b": LLAMA_32_1B,
    "llama-3.2-3b": LLAMA_32_3B,
    "llama-tiny": LLAMA_TINY,
    "llama-tiny-64": LLAMA_TINY_64,
    "mixtral-8x7b": MIXTRAL_8X7B,
    "moe-tiny": MOE_TINY,
    "qwen-2.5-7b": QWEN25_7B,
    "qwen-3-8b": QWEN3_8B,
    "qwen-3-30b-a3b": QWEN3_30B_A3B,
    "mistral-7b": MISTRAL_7B,
    "gemma-2b": GEMMA_2B,
    "gemma-2-2b": GEMMA2_2B,
    "gemma-3-1b": GEMMA3_1B,
    "gemma-3-4b": GEMMA3_4B,
    "llama-4-scout": LLAMA4_SCOUT,
    "deepseek-v2-lite": DEEPSEEK_V2_LITE,
    "deepseek-v3": DEEPSEEK_V3,
    "mla-tiny": MLA_TINY,
    "scmoe-tiny": SCMOE_TINY,
    "linear-tiny": LINEAR_TINY,
    "conv-tiny": CONV_TINY,
    "ssm-tiny": SSM_TINY,
    "glm-4-9b": GLM_4_9B,
    "olmo-2-7b": OLMO2_7B,
    "command-r-35b": COMMAND_R_35B,
    "minitron-4b": MINITRON_4B,
    "starcoder2-7b": STARCODER2_7B,
    "gpt-oss-20b": GPT_OSS_20B,
    "gpt-oss-120b": GPT_OSS_120B,
}


#: the leaves of a layer of several sublayers (``sublayers`` > 1) that
#: are the layer's own, one a layer: its router and its experts; every
#: other leaf is a sublayer's, in its sub-tree ``sub<i>``
EXPERT_LEAVES = ("w_router", "router_bias", "w_gate", "w_up", "w_down")


def param_specs(config: LlamaConfig) -> dict:
    """Logical-axis tree matching :func:`init_params` output."""
    L = ("layers",)
    if config.mla:
        # MLA: the latent projections are skinny (rank ≪ hidden), so
        # only the per-head b-projections shard over tp ("heads")
        attn = {
            "wkv_a": L + ("embed_fsdp", None),
            "kv_a_norm": L + (None,),
            "wkv_b": L + (None, "heads"),
            "wo": L + ("heads", "embed_fsdp"),
        }
        if config.q_lora_rank:
            attn["wq_a"] = L + ("embed_fsdp", None)
            attn["q_a_norm"] = L + (None,)
            attn["wq_b"] = L + (None, "heads")
        else:
            attn["wq"] = L + ("embed_fsdp", "heads")
        if config.attn_gate:
            attn["w_og"] = L + ("embed_fsdp", "heads")
        if config.index_topk:  # skinny, like the latents: replicated
            attn["wq_idx"] = L + (None, None)
            attn["wk_idx"] = L + ("embed_fsdp", None)
            attn["k_idx_norm"] = L + (None,)
            attn["w_idx"] = L + ("embed_fsdp", None)
    else:
        attn = {
            "wq": L + ("embed_fsdp", "heads"),
            "wk": L + ("embed_fsdp", "kv_heads"),
            "wv": L + ("embed_fsdp", "kv_heads"),
            "wo": L + ("heads", "embed_fsdp"),
        }
        if config.attn_gate:
            attn["w_og"] = L + ("embed_fsdp", "heads")
    N = (
        (None, None)
        if config.norm_type in ("layernorm1p", "layernorm_bias")
        else (None,)
    )
    dense_mlp = {
        "w_up": L + ("embed_fsdp", "mlp"),
        "w_down": L + ("mlp", "embed_fsdp"),
    }
    if not config.mlp_gateless:
        dense_mlp["w_gate"] = L + ("embed_fsdp", "mlp")
    if config.pre_norm and not config.parallel_block:
        # Cohere's parallel block shares attn_norm (one real leaf)
        dense_mlp["mlp_norm"] = L + N
    if config.n_experts:
        mlp = {
            "w_router": L + ("embed_fsdp", None),
            "w_gate": L + ("experts", "embed_fsdp", "mlp"),
            "w_up": L + ("experts", "embed_fsdp", "mlp"),
            "w_down": L + ("experts", "mlp", "embed_fsdp"),
        }
        if config.pre_norm and not config.parallel_block:
            mlp["mlp_norm"] = L + N
        if config.router_bias:
            mlp["router_bias"] = L + (None,)
        if config.moe_bias:
            mlp["b_router"] = L + (None,)
            mlp["b_gate"] = L + ("experts", "mlp")
            mlp["b_up_e"] = L + ("experts", "mlp")
            mlp["b_down_e"] = L + ("experts", None)
        if config.moe_shared_expert:  # dense: shard like a plain MLP
            mlp["w_shared_gate"] = L + ("embed_fsdp", "mlp")
            mlp["w_shared_up"] = L + ("embed_fsdp", "mlp")
            mlp["w_shared_down"] = L + ("mlp", "embed_fsdp")
    else:
        mlp = dense_mlp
    layer = {**attn, **mlp}
    if config.pre_norm:
        layer["attn_norm"] = L + N
    if config.qkv_bias:
        layer["bq"] = L + ("heads",)
        layer["bk"] = L + ("kv_heads",)
        layer["bv"] = L + ("kv_heads",)
    if config.proj_bias:  # StarCoder2 / gpt-oss
        layer["bo"] = L + (None,)
        if not config.n_experts:  # dense-MLP biases only
            layer["b_up"] = L + ("mlp",)
            layer["b_down"] = L + (None,)
    if config.wo_bias:
        layer["bo"] = L + (None,)
    if config.diff_attn:  # four lambda vectors and the pair's sub-norm
        layer["diff_lam"] = L + (None, None)
        layer["diff_norm"] = L + (None,)
    if config.attn_sinks:
        layer["sinks"] = L + ("heads",)
    if config.qk_norm:
        if config.norm_type == "layernorm":  # Cohere [H, D] weights
            layer["q_norm"] = L + ("heads", None)
            layer["k_norm"] = L + ("kv_heads", None)
        else:
            layer["q_norm"] = L + (None,)
            layer["k_norm"] = L + (None,)
    if config.qk_norm_flat:  # OLMo-2: full projection width
        layer["q_norm"] = L + ("heads",)
        layer["k_norm"] = L + ("kv_heads",)
    if config.post_norms:
        layer["attn_post_norm"] = L + (None,)
        layer["mlp_post_norm"] = L + (None,)
    if config.sublayers > 1:
        # the router and the experts are the layer's; every other leaf
        # is a sublayer's, beside its dense FFN under the plain names
        sub = {
            **{k: v for k, v in layer.items() if k not in EXPERT_LEAVES},
            **dense_mlp,
        }
        layer = {
            **{k: v for k, v in layer.items() if k in EXPERT_LEAVES},
            **{f"sub{i}": dict(sub) for i in range(config.sublayers)},
        }
    specs = {
        "embed": ("vocab", "embed_fsdp"),
        "layers": layer,
        "final_norm": N,
    }
    if config.first_k_dense:
        # DeepSeek dense prelude: same attention, plain-MLP FFN
        specs["dense_layers"] = {
            k: v for k, v in {**layer, **dense_mlp}.items()
            if k not in ("w_router", "router_bias", "w_shared_gate",
                         "w_shared_up", "w_shared_down")
        }
    if "window" in config.layer_types:
        # the same leaves at the window shape, the indexer's left out
        specs["window_layers"] = {
            k: v for k, v in layer.items() if "idx" not in k
        }
    # what a layer's attention adds to its norms and MLP (a mixer's, a
    # gmu's or a cross layer's leaves take its place)
    attn_side = set(attn) | {
        "q_norm", "k_norm", "bq", "bk", "bv", "bo", "diff_lam", "diff_norm",
    }
    if "gmu" in config.layer_types:
        specs["gmu_layers"] = {
            **{k: v for k, v in layer.items() if k not in attn_side},
            "gmu_w1": L + ("embed_fsdp", None), "wo": L + (None, "embed_fsdp"),
        }
    if "cross" in config.layer_types:  # the queries' side of the attention
        specs["cross_layers"] = {
            k: v for k, v in layer.items() if k not in ("wk", "wv", "bk", "bv")
        }
    for kind in STATE_KINDS:
        if kind not in config.layer_types:
            continue
        # a mixer's leaves in place of the attention's (skinny or
        # elementwise ones replicated, a linear mixer's projections over
        # heads; a conv or mamba mixer's are not split by heads)
        mixer = {
            k: L + (
                (None,) * (len(shape) - 1) if init == "conv" or len(shape) < 3
                else ("heads", "embed_fsdp") if k == "wo" and kind == "linear"
                else ("embed_fsdp", "heads") if kind == "linear"
                else (None, "embed_fsdp") if k == "wo" else ("embed_fsdp", None)
            )
            for k, (shape, init) in mixer_of(kind).leaf_shapes(config, 1).items()
        }
        swap = lambda tree: {
            **{k: v for k, v in tree.items() if k not in attn_side},
            **mixer,
        }
        if config.n_kind(kind, prelude=False):
            specs[STACK_OF[kind]] = swap(layer)
        if config.first_k_dense and config.prelude_kind == kind:
            specs["dense_layers"] = swap(specs["dense_layers"])
    if not config.tie_embeddings:
        specs["lm_head"] = ("embed_fsdp", "vocab")
    return specs


def _init_mixer(
    c: LlamaConfig, kind: str, key: jax.Array, L: int, std: float, depth: int
) -> dict:
    """A stack of ``L`` mixers of ``kind`` (its module states the
    leaves: models/kda.py, models/shortconv.py)."""
    out = {}
    shapes = mixer_of(kind).leaf_shapes(c, L)
    for i, (name, (shape, init)) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, 41 + i)
        if init == "ones":
            out[name] = jnp.ones(shape, c.dtype)
        elif init == "zeros":
            out[name] = jnp.zeros(shape, c.dtype)
        elif init == "small":
            out[name] = jax.random.normal(k, shape, jnp.float32) * std
        else:
            scale = {
                "normal": std, "out": std / math.sqrt(2 * depth),
                "conv": shape[1]**-0.5,  # its taps
            }[init]
            out[name] = (
                jax.random.normal(k, shape, jnp.float32) * scale
            ).astype(c.dtype)
    return out


def _init_attn(
    c: LlamaConfig, key: jax.Array, L: int, std: float, depth: int = 0
) -> dict:
    """Attention projections for an L-layer stack (standard or MLA);
    ``depth``: the model's layer count where ``c`` is one group's."""
    dt = c.dtype
    k = jax.random.split(key, 8)

    def normal(key, shape, scale=std):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dt)

    wo_scale = std / math.sqrt(2 * max(1, depth or c.n_layers))
    if c.mla:
        attn = {
            "wkv_a": normal(
                k[2], (L, c.hidden_size, c.kv_lora_rank + c.qk_rope_head_dim)
            ),
            "kv_a_norm": jnp.ones((L, c.kv_lora_rank), dt),
            "wkv_b": normal(
                k[3],
                (L, c.kv_lora_rank,
                 c.n_heads * (c.qk_nope_head_dim + c.v_head_dim)),
            ),
            "wo": normal(k[4], (L, c.o_dim, c.hidden_size), wo_scale),
        }
        if c.q_lora_rank:
            attn["wq_a"] = normal(k[1], (L, c.hidden_size, c.q_lora_rank))
            attn["q_a_norm"] = jnp.ones((L, c.q_lora_rank), dt)
            # distinct stream: k[5..7] are the MLP draws in init_params
            attn["wq_b"] = normal(
                jax.random.fold_in(key, 21), (L, c.q_lora_rank, c.q_dim)
            )
        else:
            attn["wq"] = normal(k[1], (L, c.hidden_size, c.q_dim))
        if c.attn_gate:
            attn["w_og"] = normal(
                jax.random.fold_in(key, 22), (L, c.hidden_size, c.n_heads)
            )
        if c.index_topk:
            hi, di = c.index_n_heads, c.index_head_dim
            attn["wq_idx"] = normal(
                jax.random.fold_in(key, 23),
                (L, c.q_lora_rank or c.hidden_size, hi * di),
            )
            attn["wk_idx"] = normal(
                jax.random.fold_in(key, 24), (L, c.hidden_size, di)
            )
            attn["k_idx_norm"] = jnp.ones((L, di), dt)
            attn["w_idx"] = normal(
                jax.random.fold_in(key, 25), (L, c.hidden_size, hi)
            )
        return attn
    attn = {
        "wq": normal(k[1], (L, c.hidden_size, c.q_dim)),
        "wk": normal(k[2], (L, c.hidden_size, c.kv_dim)),
        "wv": normal(k[3], (L, c.hidden_size, c.kv_dim)),
        "wo": normal(k[4], (L, c.q_dim, c.hidden_size), wo_scale),
    }
    if c.qkv_bias:
        attn["bq"] = jnp.zeros((L, c.q_dim), dt)
        attn["bk"] = jnp.zeros((L, c.kv_dim), dt)
        attn["bv"] = jnp.zeros((L, c.kv_dim), dt)
    if c.attn_gate:
        attn["w_og"] = normal(
            jax.random.fold_in(key, 22), (L, c.hidden_size, c.n_heads)
        )
    if c.wo_bias:
        attn["bo"] = jnp.zeros((L, c.hidden_size), dt)
    if c.diff_attn:
        # (lambda_q1, lambda_k1, lambda_q2, lambda_k2); the sub-norm's
        # weight as (w - 1): zeros are identity
        attn["diff_lam"] = normal(jax.random.fold_in(key, 26), (L, 4, c.head_dim))
        attn["diff_norm"] = jnp.zeros((L, 2 * c.head_dim), dt)
    return attn


def init_params(config: LlamaConfig, key: jax.Array, depth: int = 0) -> dict:
    """``depth``: the whole model's layer count where ``config`` is one
    group of its layers (the residual projections' init scale)."""
    c = config
    k = jax.random.split(key, 8)
    std = 0.02
    dt = c.dtype
    depth = depth or c.n_layers
    n_win = c.layer_types.count("window")

    def normal(key, shape, scale=std):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dt)

    def norm_init(shape):
        if c.norm_type in ("layernorm1p", "layernorm_bias"):
            # stacked (scale, bias); Nemotron's 1p stores scale-1 so
            # zeros are identity there, ones-row for plain LayerNorm
            z = jnp.zeros(shape[:-1] + (2, shape[-1]), dt)
            if c.norm_type == "layernorm_bias":
                z = z.at[..., 0, :].set(1.0)
            return z
        # Gemma-style norms scale by (1 + w): identity init is w = 0
        return (jnp.zeros if c.norm_offset else jnp.ones)(shape, dt)

    L = c.n_kind("full", prelude=False)
    if c.n_experts:
        E, EH = c.n_experts + c.zero_experts, c.n_experts_held
        mlp = {
            "mlp_norm": norm_init((L, c.hidden_size)),
            "w_router": normal(
                jax.random.fold_in(key, 7), (L, c.hidden_size, E)
            ),
            "w_gate": normal(k[5], (L, EH, c.hidden_size, c.intermediate_size)),
            "w_up": normal(k[6], (L, EH, c.hidden_size, c.intermediate_size)),
            "w_down": normal(
                k[7], (L, EH, c.intermediate_size, c.hidden_size), std / math.sqrt(2 * depth)
            ),
        }
        if c.moe_shared_expert:  # Llama4/DeepSeek dense shared expert
            FS = c.moe_shared_intermediate or c.intermediate_size
            mlp["w_shared_gate"] = normal(
                jax.random.fold_in(key, 11), (L, c.hidden_size, FS)
            )
            mlp["w_shared_up"] = normal(
                jax.random.fold_in(key, 12), (L, c.hidden_size, FS)
            )
            mlp["w_shared_down"] = normal(
                jax.random.fold_in(key, 13),
                (L, FS, c.hidden_size), std / math.sqrt(2 * depth),
            )
    else:
        mlp = {
            "mlp_norm": norm_init((L, c.hidden_size)),
            "w_up": normal(k[6], (L, c.hidden_size, c.intermediate_size)),
            "w_down": normal(k[7], (L, c.intermediate_size, c.hidden_size), std / math.sqrt(2 * depth)),
        }
        if not c.mlp_gateless:
            mlp["w_gate"] = normal(
                k[5], (L, c.hidden_size, c.intermediate_size)
            )
    if c.n_experts and c.router_bias:
        mlp["router_bias"] = jnp.zeros(
            (L, c.n_experts + c.zero_experts), jnp.float32
        )
    if c.n_experts and c.moe_bias:
        mlp["b_router"] = jnp.zeros((L, c.n_experts), jnp.float32)
        mlp["b_gate"] = jnp.zeros((L, c.n_experts, c.intermediate_size), dt)
        mlp["b_up_e"] = jnp.zeros((L, c.n_experts, c.intermediate_size), dt)
        mlp["b_down_e"] = jnp.zeros((L, c.n_experts, c.hidden_size), dt)
    if not c.pre_norm or c.parallel_block:
        # OLMo-2 has no input norms; Cohere's parallel block shares
        # attn_norm for both sublayers (one real leaf)
        mlp.pop("mlp_norm", None)
    params = {
        "embed": normal(k[0], (c.vocab_size, c.hidden_size)),
        "layers": {
            # pass the ORIGINAL key: _init_attn re-splits it to k[1..4],
            # reproducing the exact pre-refactor draws (seed-stable)
            **_init_attn(c, key, L, std, depth),
            **mlp,
        },
        "final_norm": norm_init((c.hidden_size,)),
    }
    if c.pre_norm:
        params["layers"]["attn_norm"] = norm_init((L, c.hidden_size))
    if c.proj_bias:  # StarCoder2 / gpt-oss
        params["layers"]["bo"] = jnp.zeros((L, c.hidden_size), dt)
        if not c.n_experts:
            params["layers"]["b_up"] = jnp.zeros((L, c.intermediate_size), dt)
            params["layers"]["b_down"] = jnp.zeros((L, c.hidden_size), dt)
    if c.attn_sinks:
        params["layers"]["sinks"] = jnp.zeros((L, c.n_heads), jnp.float32)
    if c.qk_norm:
        if c.norm_type == "layernorm":  # Cohere per-head weights
            params["layers"]["q_norm"] = jnp.ones((L, c.n_heads, c.head_dim), dt)
            params["layers"]["k_norm"] = jnp.ones((L, c.n_kv_heads, c.head_dim), dt)
        else:
            params["layers"]["q_norm"] = jnp.ones((L, c.head_dim), dt)
            params["layers"]["k_norm"] = jnp.ones((L, c.head_dim), dt)
    if c.qk_norm_flat:  # OLMo-2: full projection width
        params["layers"]["q_norm"] = jnp.ones((L, c.q_dim), dt)
        params["layers"]["k_norm"] = jnp.ones((L, c.kv_dim), dt)
    if c.post_norms:
        params["layers"]["attn_post_norm"] = norm_init((L, c.hidden_size))
        params["layers"]["mlp_post_norm"] = norm_init((L, c.hidden_size))
    if c.sublayers > 1:
        # the router and the experts stay the layer's; each sublayer is
        # a plain dense layer's leaves (attention, norms, a dense FFN)
        # in a sub-tree of its own, drawn from a key of its own
        FD = c.dense_intermediate

        def sublayer(i):
            ks = jax.random.split(jax.random.fold_in(key, 31 + i), 4)
            return {
                "attn_norm": norm_init((L, c.hidden_size)),
                **_init_attn(c, ks[0], L, std, depth),
                "mlp_norm": norm_init((L, c.hidden_size)),
                "w_gate": normal(ks[1], (L, c.hidden_size, FD)),
                "w_up": normal(ks[2], (L, c.hidden_size, FD)),
                "w_down": normal(
                    ks[3], (L, FD, c.hidden_size), std / math.sqrt(2 * depth)
                ),
            }

        params["layers"] = {
            **{k: v for k, v in params["layers"].items() if k in EXPERT_LEAVES},
            **{f"sub{i}": sublayer(i) for i in range(c.sublayers)},
        }
    if c.first_k_dense:
        # DeepSeek dense prelude: same attention, plain-MLP FFN
        K, F = c.first_k_dense, c.dense_intermediate or c.intermediate_size
        kp = jax.random.fold_in(key, 2)
        kd = jax.random.split(kp, 4)
        dense = {
            "attn_norm": norm_init((K, c.hidden_size)),
            **_init_attn(c, kd[0], K, std, depth),
            "mlp_norm": norm_init((K, c.hidden_size)),
            "w_gate": normal(kd[1], (K, c.hidden_size, F)),
            "w_up": normal(kd[2], (K, c.hidden_size, F)),
            "w_down": normal(
                kd[3], (K, F, c.hidden_size), std / math.sqrt(2 * depth)
            ),
        }
        if c.post_norms:
            dense["attn_post_norm"] = norm_init((K, c.hidden_size))
            dense["mlp_post_norm"] = norm_init((K, c.hidden_size))
        if c.prelude_kind != "full":  # a mixer in the attention's place
            dense = {
                "attn_norm": dense["attn_norm"],
                **_init_mixer(c, c.prelude_kind, kd[0], K, std, depth),
                **{k: dense[k] for k in ("mlp_norm", "w_gate", "w_up", "w_down")},
            }
        params["dense_layers"] = dense
    if n_win:
        # the window layers: the same leaves at their own attention
        # shape, a stack of their own (only the stack is kept)
        wc = dataclasses.replace(
            c.window_config, n_layers=n_win, first_k_dense=0,
            vocab_size=8, tie_embeddings=True,
        )
        params["window_layers"] = init_params(
            wc, jax.random.fold_in(key, 3), depth
        )["layers"]
    for kind, folds in (
        ("linear", (4, 5)), ("conv", (6, 8)), ("mamba", (9, 10)),
        ("gmu", (14, 15)), ("cross", (16, 17)),
    ):
        n = c.n_kind(kind, prelude=False)
        if not n:
            continue
        # the layers that do not attend over rows of their own: the
        # expert layer's MLP leaves under a mixer's (a gmu's two
        # projections, a cross layer's queries), a stack of their own
        # (only the stack is kept; the attention it is drawn with is
        # dropped: one head of the least widths, the MLP leaves' draws do
        # not read them)
        mc = dataclasses.replace(
            c, n_layers=n, layer_types=(), first_k_dense=0, vocab_size=8,
            tie_embeddings=True, n_heads=1, n_kv_heads=1, head_dim=2,
            kv_lora_rank=0, qk_norm=False, qkv_bias=False, wo_bias=False,
            diff_attn=False,
        )
        full = init_params(mc, jax.random.fold_in(key, folds[0]), depth)["layers"]
        attn = _init_attn(mc, key, 1, std, depth)
        own = jax.random.fold_in(key, folds[1])
        if kind == "gmu":
            di = c.ssm_inner
            mixer = {
                "gmu_w1": normal(jax.random.fold_in(own, 0), (n, c.hidden_size, di)),
                "wo": normal(
                    jax.random.fold_in(own, 1), (n, di, c.hidden_size),
                    std / math.sqrt(2 * depth),
                ),
            }
        elif kind == "cross":
            mixer = {
                k: v for k, v in _init_attn(c, own, n, std, depth).items()
                if k not in ("wk", "wv", "bk", "bv")
            }
        else:
            mixer = _init_mixer(c, kind, own, n, std, depth)
        params[STACK_OF[kind]] = {
            **{k: v for k, v in full.items() if k not in attn}, **mixer,
        }
    if not c.tie_embeddings:
        params["lm_head"] = normal(jax.random.fold_in(key, 99), (c.hidden_size, c.vocab_size))
    return params


def rms_norm(
    x: jax.Array, w: jax.Array, eps: float, offset: bool = False
) -> jax.Array:
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    if offset:  # Gemma convention: stored weight is (scale - 1)
        w = 1.0 + w.astype(jnp.float32)
        return ((x32 * rms) * w).astype(x.dtype)
    return (x32 * rms).astype(x.dtype) * w


def layer_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """Mean-centered, weight-only LayerNorm in f32 (Cohere). ``w`` may
    carry leading broadcast dims (per-head qk norms store [H, D])."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
    return out.astype(x.dtype)


def model_norm(x: jax.Array, w: jax.Array, config: "LlamaConfig") -> jax.Array:
    """The model's norm flavor: RMSNorm (with the Gemma offset
    convention), Cohere's mean-centered LayerNorm, or Nemotron's
    LayerNorm1P — (1 + w)·norm(x) + b with ``w`` stacked [..., 2, H]
    as (scale-1, bias)."""
    if config.norm_type == "layernorm":
        return layer_norm(x, w, config.norm_eps)
    if config.norm_type in ("layernorm1p", "layernorm_bias"):
        # stacked [..., 2, H] = (scale row, bias row); Nemotron's 1p
        # stores scale-1, StarCoder2's plain LayerNorm stores scale
        x32 = x.astype(jnp.float32)
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
        scale = w[..., 0, :].astype(jnp.float32)
        if config.norm_type == "layernorm1p":
            scale = 1.0 + scale
        bias = w[..., 1, :].astype(jnp.float32)
        out = (x32 - mu) * jax.lax.rsqrt(var + config.norm_eps) * scale + bias
        return out.astype(x.dtype)
    return rms_norm(x, w, config.norm_eps, offset=config.norm_offset)


def qk_norm_apply(q, k, layer: dict, c: "LlamaConfig"):
    """Per-head q/k norm on [B, H, T, D]: Qwen3/Gemma3 RMSNorm with a
    shared [D] weight, or Cohere per-head LayerNorm with [H, D] /
    [Hkv, D] weights."""
    if c.norm_type == "layernorm":
        return (
            layer_norm(q, layer["q_norm"][None, :, None, :], c.norm_eps),
            layer_norm(k, layer["k_norm"][None, :, None, :], c.norm_eps),
        )
    return (
        rms_norm(q, layer["q_norm"], c.norm_eps, offset=c.norm_offset),
        rms_norm(k, layer["k_norm"], c.norm_eps, offset=c.norm_offset),
    )


def act_fn(config: "LlamaConfig"):
    if config.hidden_act == "silu":
        return jax.nn.silu
    if config.hidden_act == "gelu_tanh":
        return functools.partial(jax.nn.gelu, approximate=True)
    if config.hidden_act == "relu2":  # Nemotron squared ReLU
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown hidden_act {config.hidden_act!r}")


def grouped_scan_layout(config: "LlamaConfig", xs: dict):
    """→ (g, windows, xs_main, xs_tail) for scanning mixed
    sliding/global layers.

    g == 1: uniform window, scan ``xs`` as-is (no tail). g > 1
    (Gemma2/3): every scan step runs ``g`` sublayers with static
    windows ``windows[:g]``; the stacked [L, ...] leaves reshape to
    [L//g, g, ...]. When the pattern doesn't divide the layer count
    (Gemma3: 26 layers, pattern 6) the last ``L % g`` layers come back
    as ``xs_tail`` ([r, ...] leaves) for the caller to unroll after the
    scan — their windows are ``windows[-r:]``. One source of truth for
    llama.forward and the serve engine's prefill.
    """
    windows = layer_windows(config)
    nopes = layer_nope(config)
    mixed_windows = len(set(windows)) > 1
    mixed_nope = len(set(nopes)) > 1
    if mixed_windows and mixed_nope:
        aligned = config.sliding_pattern == config.nope_pattern and all(
            (w == 0) == n for w, n in zip(windows, nopes)
        )
        if not aligned:
            raise ValueError(
                "mixed sliding windows and NoPE layers are only "
                "supported when aligned (Cohere2: the global layers "
                "ARE the NoPE layers, same period)"
            )
    g = (
        config.sliding_pattern if mixed_windows
        else config.nope_pattern if mixed_nope
        else 1
    )
    if g == 1:
        return g, windows, xs, None
    r = config.n_layers % g
    n_main = config.n_layers - r
    xs_main = jax.tree.map(
        lambda a: a[:n_main].reshape((n_main // g, g) + a.shape[1:]), xs
    )
    xs_tail = jax.tree.map(lambda a: a[n_main:], xs) if r else None
    return g, windows, xs_main, xs_tail


def sublayer(group, i: int, g: int):
    """Sublayer ``i`` of a grouped scan step (identity when g == 1)."""
    return jax.tree.map(lambda a: a[i], group) if g > 1 else group


def layer_windows(config: "LlamaConfig") -> list[int]:
    """Static per-layer attention window (0 = full/global attention).

    ``sliding_pattern == p`` (Gemma2: p=2) makes the last layer of every
    group of ``p`` global and the others sliding; otherwise the window is
    uniform across layers (Mistral).
    """
    c = config
    if c.layer_types:
        return [c.sliding_window if t == "window" else 0 for t in c.layer_types]
    if not c.sliding_window:
        return [0] * c.n_layers
    p = c.sliding_pattern
    if p and p > 1:
        return [
            0 if i % p == p - 1 else c.sliding_window
            for i in range(c.n_layers)
        ]
    return [c.sliding_window] * c.n_layers


def layer_nope(config: "LlamaConfig") -> list[bool]:
    """Static per-layer NoPE flag: every ``nope_pattern``-th layer
    (Llama4: 4) skips rope and attends globally. ``nope_pattern == 1``
    means EVERY layer is NoPE (an all-zeros ``no_rope_layers``
    checkpoint); 0 disables NoPE entirely."""
    c = config
    if not c.nope_pattern:
        return [False] * c.n_layers
    return [(i + 1) % c.nope_pattern == 0 for i in range(c.n_layers)]


class LayerRun(NamedTuple):
    """Consecutive layers of one group, ``params[key][lo:hi]``."""

    key: str  # "dense_layers" | a stack of :data:`STACK_OF`
    config: "LlamaConfig"  # the group's attention shape
    window: int  # 0 = full attention
    lo: int
    hi: int
    kind: str = "full"  # a key of :data:`STACK_OF`: what its layers keep


def layer_runs(config: "LlamaConfig") -> list:
    """The order in which a model of layer GROUPS walks its stacked
    weights: the ``first_k_dense`` prelude, then maximal runs of
    consecutive layers of one kind (``layer_types``), each a slice of
    its group's stack. A model of one kind of layer is the case of one
    run after the prelude. One source of truth for ``forward``, the
    serve engine's latent programs and its cache layout."""
    c = config
    runs = []
    if c.first_k_dense:
        runs.append(
            LayerRun("dense_layers", c, 0, 0, c.first_k_dense, c.prelude_kind)
        )
    kinds = c.layer_types[c.first_k_dense:] or ("full",) * (
        c.n_layers - c.first_k_dense
    )
    seen = dict.fromkeys(STACK_OF, 0)
    for kind in kinds:
        at = seen[kind]
        seen[kind] += 1
        last = runs[-1] if runs else None
        key = STACK_OF[kind]
        if last is not None and last.key == key and last.hi == at:
            runs[-1] = last._replace(hi=at + 1)
        elif kind == "window":
            runs.append(
                LayerRun(key, c.window_config, c.sliding_window, at, at + 1, kind)
            )
        else:
            runs.append(LayerRun(key, c, 0, at, at + 1, kind))
    return runs


def run_row(c: "LlamaConfig", run: LayerRun) -> int:
    """The run's first layer → its row in its kind's cache buffers: a
    kind's layers in the order the model walks them, the prelude's
    first."""
    ahead = run.key != "dense_layers" and run.kind == c.prelude_kind
    return run.lo + (c.first_k_dense if ahead else 0)


def run_slice(stack: dict, run: LayerRun) -> dict:
    """The run's layers of its group's stack (the stack itself where
    the run is all of it: no slice is traced)."""
    n = jax.tree.leaves(stack)[0].shape[0]
    if (run.lo, run.hi) == (0, n):
        return stack
    return jax.tree.map(lambda a: a[run.lo : run.hi], stack)


class LayerPeriods(NamedTuple):
    """:func:`layer_runs` folded where the runs repeat: ``head`` (the
    prelude) walked once, then ``count`` times the runs of ``period``
    (given as the FIRST period's; period ``i``'s run lies ``i *
    per[run.key]`` layers further along its stack), then ``tail``."""

    head: list
    period: list
    count: int
    per: dict  # stack key → layers of it in one period
    tail: list


def _fold_runs(rest: list, anywhere: bool) -> tuple:
    """The shortest pattern of (stack, length) that repeats over most of
    ``rest`` → (runs before it, its period, its count, runs after it);
    ``anywhere``: it may start past the first run (else at it)."""
    shape = lambda r: (r.key, r.hi - r.lo)
    at_best, p_best, n_best = 0, 0, 0
    for at in range(len(rest) if anywhere else 1):
        for p in range(1, (len(rest) - at) // 2 + 1):
            n = 1
            while at + (n + 1) * p <= len(rest) and all(
                shape(rest[at + n * p + j]) == shape(rest[at + j]) for j in range(p)
            ):
                n += 1
            if n > 1 and n * p > n_best * p_best:
                at_best, p_best, n_best = at, p, n
    end = at_best + p_best * n_best
    return rest[:at_best], rest[at_best : at_best + p_best], n_best, rest[end:]


def _periods_of(head: list, period: list, count: int, tail: list) -> LayerPeriods:
    per: dict = {}
    for r in period:
        per[r.key] = per.get(r.key, 0) + r.hi - r.lo
    return LayerPeriods(head, period, count, per, tail)


def layer_periods(config: "LlamaConfig") -> LayerPeriods:
    """The runs after the prelude as the shortest pattern of (stack,
    length) that repeats over most of them: a program that scans over
    the periods, its body one period, does not grow with depth (13
    layers of full·dense, then window × 3 + full three times, are a
    prelude and three periods of two runs, not seven runs). Where
    nothing repeats ``count`` is 0 and every run is in ``tail``. (The
    FIRST fold of :func:`layer_segments`: a model of one pattern.)"""
    runs = layer_runs(config)
    head = [r for r in runs if r.key == "dense_layers"]
    _, period, count, tail = _fold_runs(runs[len(head):], anywhere=False)
    return _periods_of(head, period, count, tail)


def layer_segments(config: "LlamaConfig") -> list:
    """:func:`layer_periods`, and its tail folded again wherever a
    pattern repeats in it → a list of :class:`LayerPeriods`, walked one
    after the other (each one's ``tail`` empty but the last's): a model
    of two patterns, (mamba, window) x 8, then mamba, full, then (gmu,
    cross) x 7, is two folded segments and not one with a tail of 16
    unrolled runs. A model of one pattern is one segment."""
    out = [layer_periods(config)]
    while True:
        last = out[-1]
        head, period, count, tail = _fold_runs(last.tail, anywhere=True)
        if not count:
            return out
        out[-1] = last._replace(tail=[])
        out.append(_periods_of(head, period, count, tail))


def l2_norm(x: jax.Array, eps: float) -> jax.Array:
    """Weightless rms normalization in f32 (Llama4 qk norm)."""
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms).astype(x.dtype)


def attn_temp_scales(positions: jax.Array, config: "LlamaConfig") -> jax.Array:
    """Llama4 NoPE-layer query temperature tuning → [T] f32:
    1 + attn_temp_scale * log1p(floor((pos+1)/floor_scale))."""
    p = positions.astype(jnp.float32)
    return (
        jnp.log1p(jnp.floor((p + 1.0) / config.attn_temp_floor))
        * config.attn_temp_scale
        + 1.0
    )


def rope_freqs(
    positions: jax.Array,
    head_dim: int,
    theta: float,
    scaling: Optional[tuple] = None,
) -> tuple[jax.Array, jax.Array]:
    """positions [T] → (cos, sin) each [T, head_dim//2], f32.

    ``scaling`` applies the Llama-3.1 "llama3" rope rescaling
    (factor, low_freq_factor, high_freq_factor, original_context):
    long-wavelength frequencies are divided by ``factor``, short ones
    kept, with a smooth ramp between — matching HF's
    ``rope_type: llama3`` so 3.1/3.2 checkpoints decode correctly.
    The tagged form ("linear", factor) divides every frequency by
    ``factor`` (HF ``rope_type: linear``, Gemma3's global layers).
    The tagged form ("yarn", factor, beta_fast, beta_slow, orig_ctx,
    attention_factor) is NTK-by-parts YaRN (DeepSeek checkpoints),
    mirroring HF ``_compute_yarn_parameters`` with truncate=True; the
    precomputed ``attention_factor`` multiplies cos/sin.
    """
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if scaling is not None and scaling[0] == "linear":
        inv = inv / float(scaling[1])
    elif scaling is not None and scaling[0] == "yarn":
        _, factor, beta_fast, beta_slow, orig_ctx, att_f = scaling[:6]
        truncate = scaling[6] if len(scaling) > 6 else True

        def corr_dim(rot):  # dim whose wavelength fits `rot` rotations
            return (
                head_dim * math.log(orig_ctx / (rot * 2 * math.pi))
            ) / (2 * math.log(theta))

        if truncate:  # HF floor/ceils the correction range by default
            low = max(math.floor(corr_dim(beta_fast)), 0)
            high = min(math.ceil(corr_dim(beta_slow)), head_dim - 1)
        else:  # gpt-oss: truncate=false keeps the raw boundaries
            low = max(corr_dim(beta_fast), 0)
            high = min(corr_dim(beta_slow), head_dim - 1)
        if low == high:
            high += 0.001  # HF's singularity guard
        ramp = jnp.clip(
            (jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / (high - low),
            0.0, 1.0,
        )
        # low dims (fast rotations): extrapolate (keep inv); high dims:
        # interpolate (inv / factor); ramp blends between
        inv = (inv / factor) * ramp + inv * (1.0 - ramp)
        ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
        return jnp.cos(ang) * att_f, jnp.sin(ang) * att_f
    elif scaling is not None:
        if scaling[0] == "llama3":
            scaling = scaling[1:]
        factor, low_f, high_f, orig_ctx = scaling
        wavelen = 2.0 * math.pi / inv
        smooth = (orig_ctx / wavelen - low_f) / (high_f - low_f)
        smooth = jnp.clip(smooth, 0.0, 1.0)
        inv = (1.0 - smooth) * inv / factor + smooth * inv
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def dual_rope_freqs(
    config: "LlamaConfig", positions: jax.Array
) -> tuple[tuple, tuple]:
    """→ ((cos, sin), (cos_local, sin_local)) for the config's global
    and sliding-window layers. Single-rope families get the same pair
    twice (no extra compute — the arrays are shared); Gemma3 sliding
    layers rotate with the unscaled ``rope_local_theta`` while global
    layers use ``rope_theta`` + ``rope_scaling``."""
    g = rope_freqs(
        positions, config.rope_dim, config.rope_theta, config.rope_scaling
    )
    if not config.rope_local_theta:
        return g, g
    return g, rope_freqs(
        positions, config.rope_dim_local, config.rope_local_theta
    )


def layer_rope(ropes: tuple[tuple, tuple], config: "LlamaConfig", window: int):
    """Pick a layer's (cos, sin) from :func:`dual_rope_freqs` output by
    its STATIC window (sliding layers → local rope)."""
    return ropes[1] if window else ropes[0]


def rope_partial(apply, x: jax.Array, cos: jax.Array) -> jax.Array:
    """GLM partial rotary, shared by every rope applier (train forward,
    engine decode/prefill/verify): when cos/sin are narrower than D/2,
    ``apply`` rotates only the first ``2·cos.shape[-1]`` dims and the
    tail passes through — ONE place owns the split convention."""
    rd = 2 * cos.shape[-1]
    if rd >= x.shape[-1]:
        return apply(x)
    return jnp.concatenate([apply(x[..., :rd]), x[..., rd:]], axis=-1)


def apply_rope(
    x: jax.Array, cos: jax.Array, sin: jax.Array, interleaved: bool = False
) -> jax.Array:
    """x [B, H, T, D]; rotate-half convention, or Meta/Llama4's
    interleaved complex-pair rotation when ``interleaved``.

    When cos/sin are narrower than D/2 (GLM partial rotary), only the
    leading dims rotate (see :func:`rope_partial`)."""
    if 2 * cos.shape[-1] < x.shape[-1]:
        return rope_partial(
            lambda xx: apply_rope(xx, cos, sin, interleaved), x, cos
        )
    if interleaved:
        x1 = x[..., 0::2]
        x2 = x[..., 1::2]
        c = cos[None, None].astype(x.dtype)
        s = sin[None, None].astype(x.dtype)
        out = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
        return out.reshape(x.shape)
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c = cos[None, None].astype(x.dtype)
    s = sin[None, None].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _proj(
    layer: dict, name: str, inp: jax.Array, eq: str, eq_a: str, eq_b: str
) -> jax.Array:
    """Base matmul + optional LoRA bypass: x·W + s·(x·A)·B.
    The low-rank path stays unfused from W (two skinny matmuls) —
    cheaper on MXU than materializing W+ΔW per step. One helper for all
    seven adaptable projections.

    Weight-only int8 (models/quant.py): when ``name_q``/``name_s``
    replace ``name``, the int8 weight casts into the matmul and the
    per-output-channel scale multiplies the result — XLA fuses both
    into the dot, and HBM reads half the bytes."""
    w = layer.get(name)
    if w is not None:
        y = jnp.einsum(eq, inp, w)
    else:
        y = jnp.einsum(eq, inp, layer[f"{name}_q"].astype(inp.dtype))
        y = y * layer[f"{name}_s"].astype(y.dtype)
    a, b = layer.get(f"{name}_lora_a"), layer.get(f"{name}_lora_b")
    if a is not None and b is not None:
        y = y + jnp.einsum(eq_b, jnp.einsum(eq_a, inp, a), b) * layer["lora_scale"]
    return y


def mla_rescale(x: jax.Array, rank: int, c: LlamaConfig) -> jax.Array:
    """``mla_lora_rescale``: a normed latent of width ``rank`` scales by
    sqrt(hidden / rank), a constant (no-op when off)."""
    if not c.mla_lora_rescale:
        return x
    return x * jnp.asarray((c.hidden_size / rank) ** 0.5, x.dtype)


def mla_q_latent(h: jax.Array, layer: dict, c: LlamaConfig) -> jax.Array:
    """Normed hidden → the low-rank query latent c_q [B, T, q_lora_rank]
    (normed, rescaled): what ``wq_b`` and the indexer's ``wq_idx`` read."""
    qa = _proj(layer, "wq_a", h, "bte,er->btr", "bte,ex->btx", "btx,xr->btr")
    qa = rms_norm(qa, layer["q_a_norm"], c.norm_eps)
    return mla_rescale(qa, c.q_lora_rank, c)


def index_qkw(
    h: jax.Array,  # [B, T, H] normed hidden
    qa: Optional[jax.Array],  # [B, T, q_lora_rank] (None: no low-rank query)
    layer: dict,
    c: LlamaConfig,
    rope,  # [B, Hh, T, D] -> the same, the caller's rope at its positions
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The sparse indexer's three projections (DeepSeek-V3.2's
    ``Indexer`` without its fp8 cast and Hadamard rotation) →
    (q_I [B, Hi, T, Di], k_I [B, T, Di], w [B, T, Hi] f32): rope on the
    first ``qk_rope_head_dim`` dims of q_I and k_I, k_I LayerNormed,
    w scaled by Hi^-1/2 · Di^-1/2. k_I is what the serve engine caches."""
    b, t, _ = h.shape
    hi, di = c.index_n_heads, c.index_head_dim
    src = h if qa is None else qa
    q_i = jnp.einsum("btr,rd->btd", src, layer["wq_idx"])
    q_i = rope(q_i.reshape(b, t, hi, di).transpose(0, 2, 1, 3))
    k_i = jnp.einsum("bte,ed->btd", h, layer["wk_idx"])
    k_i = layer_norm(k_i, layer["k_idx_norm"], c.norm_eps)
    k_i = rope(k_i[:, None])[:, 0]
    w = jnp.einsum(
        "bte,eh->bth", h, layer["w_idx"], preferred_element_type=jnp.float32
    ) * (hi**-0.5 * di**-0.5)
    return q_i, k_i, w


def index_select(
    q_i: jax.Array,  # [B, Hi, Tq, Di]
    w: jax.Array,  # [B, Tq, Hi] f32
    k_i: jax.Array,  # [B, Tk, Di]
    visible: jax.Array,  # [B, Tq, Tk] bool: the causal keys
    topk: int,
) -> jax.Array:
    """→ bool [B, Tq, Tk]: of each query's visible keys the ``topk``
    with the largest index score I(i, j) = sum_h w_h(i) relu(q_I,h(i) .
    k_I(j)), all of them where there are no more than ``topk``. Scores
    in f32; a tie at the cut keeps both keys."""
    with jax.named_scope("dtpu.indexer"):
        dots = jnp.einsum(
            "bhqd,bkd->bhqk", q_i, k_i, preferred_element_type=jnp.float32
        )
        # the weighted sum in exact f32 on the vector unit: as an einsum
        # the MXU would round relu(dots) and w to bf16, and sixty-four
        # signed terms that cancel leave rounding noise where the cut
        # between the topk-th and the next key is decided
        score = (jax.nn.relu(dots) * w.transpose(0, 2, 1)[..., None]).sum(1)
        score = jnp.where(visible, score, -jnp.inf)
        tk = score.shape[-1]
        if tk <= topk:
            return visible
        kth = jnp.sort(score, axis=-1)[..., tk - topk]
        return visible & (score >= kth[..., None])


def head_gate(
    o: jax.Array, h: jax.Array, layer: dict, c: LlamaConfig, eq: str
) -> jax.Array:
    """``attn_gate``: head a's output scales by sigmoid(h · w_og)[a];
    ``eq`` lays the gate [B, T, heads] out against ``o`` (``bth,bhtv
    ->bhtv``, or the engine's other layouts). No-op when off."""
    if not c.attn_gate:
        return o
    g = jax.nn.sigmoid(
        jnp.einsum(
            "bte,eh->bth", h, layer["w_og"], preferred_element_type=jnp.float32
        )
    ).astype(o.dtype)
    return jnp.einsum(eq, g, o)


def mla_qkv(
    h: jax.Array,  # [B, T, H] normed hidden
    layer: dict,
    config: LlamaConfig,
    cos: jax.Array,
    sin: jax.Array,
    qa: Optional[jax.Array] = None,  # the query latent, where the caller has it
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """DeepSeek MLA projections, non-absorbed (training/prefill) form →
    (q, k [B, Hq, T, qk_head_dim], v [B, Hq, T, v_head_dim]).

    The rope acts only on the single-head-shared ``k_pe`` slice and the
    per-head ``q_pe`` slice, in the interleaved complex-pair convention
    (matching HF ``apply_rotary_emb`` for deepseek_v2/v3). The serve
    engine uses the *absorbed* form instead (serve/engine.py): this form
    materializes full k/v for flash-kernel-friendly training.
    """
    c = config
    b, t, _ = h.shape
    if c.q_lora_rank:
        if qa is None:
            qa = mla_q_latent(h, layer, c)
        q = _proj(layer, "wq_b", qa, "btr,rd->btd", "btr,rx->btx", "btx,xd->btd")
    else:
        q = _proj(layer, "wq", h, "bte,ed->btd", "bte,er->btr", "btr,rd->btd")
    q = q.reshape(b, t, c.n_heads, c.qk_head_dim).transpose(0, 2, 1, 3)
    kv_a = _proj(layer, "wkv_a", h, "bte,ed->btd", "bte,er->btr", "btr,rd->btd")
    ckv = kv_a[..., : c.kv_lora_rank]
    k_pe = kv_a[..., c.kv_lora_rank :]  # [B, T, rope_dim], one shared head
    ckv = mla_rescale(
        rms_norm(ckv, layer["kv_a_norm"], c.norm_eps), c.kv_lora_rank, c
    )
    kv = _proj(layer, "wkv_b", ckv, "btr,rd->btd", "btr,rx->btx", "btx,xd->btd")
    kv = kv.reshape(
        b, t, c.n_heads, c.qk_nope_head_dim + c.v_head_dim
    ).transpose(0, 2, 1, 3)
    k_nope = kv[..., : c.qk_nope_head_dim]
    v = kv[..., c.qk_nope_head_dim :]
    q_nope = q[..., : c.qk_nope_head_dim]
    q_pe = apply_rope(q[..., c.qk_nope_head_dim :], cos, sin, interleaved=True)
    k_pe = apply_rope(
        k_pe.reshape(b, 1, t, c.qk_rope_head_dim), cos, sin, interleaved=True
    )
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:-1] + (c.qk_rope_head_dim,))],
        axis=-1,
    )
    return q, k, v


def diff_lambda_init(c: LlamaConfig, kind: str) -> jax.Array:
    """lambda_init of the layers of ``kind`` in the order the model walks
    them (a layer's row in its kind's buffers, the prelude's first) →
    [n] float32: 0.8 - 0.6 exp(-0.3 l), ``l`` the layer's index in the
    whole model."""
    at = [i for i, t in enumerate(c.layer_types) if t == kind]
    return jnp.asarray([0.8 - 0.6 * math.exp(-0.3 * i) for i in at], jnp.float32)


def diff_pack(q: jax.Array, k, v, c: LlamaConfig) -> tuple:
    """Differential attention's projections q [B, S, q_dim], k, v
    [B, S, kv_dim] (None: a cross layer's) → what plain grouped-query
    attention at ``c.attend_config`` takes: q [B, H, S, 2D], query head
    2p + j (q1 | q2 of pair p) zero-padded into half j of the pair's
    width, k and v [B, Hkv / 2, S, 2D], KV heads (2g, 2g + 1) side by
    side. Head 2p + j of the packed queries reads packed KV head
    (2p + j) // (2 H / Hkv) = p // (H / Hkv): its pair's."""
    b, s, _ = q.shape
    d = c.head_dim
    q = q.reshape(b, s, c.n_heads // 2, 2, d)
    zero = jnp.zeros_like(q[..., 0, :])
    q = jnp.stack([
        jnp.concatenate([q[..., 0, :], zero], axis=-1),
        jnp.concatenate([zero, q[..., 1, :]], axis=-1),
    ], axis=-2).reshape(b, s, c.n_heads, 2 * d).transpose(0, 2, 1, 3)
    if k is None:
        return q, None, None
    pair = lambda a: a.reshape(b, s, c.n_kv_heads // 2, 2 * d).transpose(0, 2, 1, 3)
    return q, pair(k), pair(v)


def diff_combine(o: jax.Array, layer: dict, c: LlamaConfig, lam0) -> jax.Array:
    """The packed heads' outputs ``o`` [B, S, H * 2D] (head 2p + j: a_j
    of pair p over [v1 | v2]) → [B, S, q_dim] for ``wo``: a1 - lambda a2,
    RMSNormed over the pair's 2D under (1 + ``diff_norm``), times
    (1 - lambda_init); ``lam0`` the layer's lambda_init (a scalar)."""
    with jax.named_scope("dtpu.diff_attn"):
        b, s, _ = o.shape
        f32 = jnp.float32
        a = o.reshape(b, s, c.n_heads // 2, 2, 2 * c.head_dim).astype(f32)
        lq1, lk1, lq2, lk2 = layer["diff_lam"].astype(f32)
        lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0
        dlt = a[..., 0, :] - lam * a[..., 1, :]
        dlt = dlt * jax.lax.rsqrt(
            jnp.mean(dlt * dlt, axis=-1, keepdims=True) + c.norm_eps
        )
        dlt = dlt * (1.0 + layer["diff_norm"].astype(f32)) * (1.0 - lam0)
        return dlt.reshape(b, s, c.q_dim).astype(o.dtype)


def gmu_mix(h: jax.Array, m: jax.Array, layer: dict) -> jax.Array:
    """A gated memory unit on the normed hidden ``h`` [B, S, H] and the
    scan output ``m`` [B, S, d_inner] of the same positions → m *
    silu(h W_1) for ``wo`` (W_2)."""
    with jax.named_scope("dtpu.gmu"):
        gate = jnp.einsum(
            "bte,ed->btd", h, layer["gmu_w1"].astype(h.dtype),
            preferred_element_type=jnp.float32,
        )
        return (m.astype(jnp.float32) * jax.nn.silu(gate)).astype(h.dtype)


def _diff_attention_block(
    x: jax.Array, layer: dict, c: LlamaConfig, window: int, lam0, kv,
    mesh: Optional[Mesh], rules: ShardingRules, attn_impl: Optional[str],
):
    """A differential-attention layer's sublayer over whole sequences →
    (out, (k, v) packed [B, Hkv / 2, T, 2D]: what the cross layers after
    a full layer read). ``kv``: the rows a cross layer reads (its own
    stack has queries alone), else None."""
    ac = c.attend_config
    b, t, _ = x.shape
    h = model_norm(x, layer["attn_norm"], c)
    proj = lambda n: _proj(
        layer, f"w{n}", h, "bte,ed->btd", "bte,er->btr", "btr,rd->btd"
    ) + (layer[f"b{n}"] if c.qkv_bias else 0)
    if kv is None:
        q, k, v = diff_pack(proj("q"), proj("k"), proj("v"), c)
    else:
        q, (k, v) = diff_pack(proj("q"), None, None, c)[0], kv
    o = attention(
        q, k, v, causal=True, scale=ac.attention_scale, impl=attn_impl,
        window=window, shard=kernel_shard(mesh, rules, b, k.shape[1]),
    )
    o = diff_combine(o.transpose(0, 2, 1, 3).reshape(b, t, ac.q_dim), layer, c, lam0)
    out = _proj(layer, "wo", o, "btd,de->bte", "btd,dr->btr", "btr,re->bte")
    if c.wo_bias:
        out = out + layer["bo"]
    return constrain(out, rules, "batch", "seq", None, mesh=mesh), (k, v)


def _attention_block(
    x: jax.Array,
    layer: dict,
    config: LlamaConfig,
    cos: jax.Array,
    sin: jax.Array,
    mesh: Optional[Mesh],
    rules: ShardingRules,
    attn_impl: Optional[str],
    window: int = 0,
    nope: bool = False,
    positions: Optional[jax.Array] = None,
) -> jax.Array:
    c = config
    b, t, _ = x.shape
    h = (
        model_norm(x, layer["attn_norm"], c)
        if c.pre_norm else x  # OLMo-2 norms the OUTPUT instead
    )
    index_mask = None
    if c.mla:
        qa = mla_q_latent(h, layer, c) if c.q_lora_rank else None
        q, k, v = mla_qkv(h, layer, c, cos, sin, qa=qa)
        if c.index_topk and t > c.index_topk:
            # past index_topk tokens the indexer's choice bites
            q_i, k_i, w_i = index_qkw(
                h, qa, layer, c,
                lambda x: apply_rope(x, cos, sin, interleaved=True),
            )
            index_mask = index_select(
                q_i, w_i, k_i, jnp.tril(jnp.ones((t, t), bool))[None],
                c.index_topk,
            )
        # zero-pad v to the qk head dim so every dispatch path below
        # (flash / ring / ulysses / XLA) sees uniform head dims — exact,
        # the padded lanes produce zeros that are sliced off after
        v_pad = c.qk_head_dim - c.v_head_dim
        if v_pad > 0:
            v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, v_pad)))
        q = constrain(q, rules, "batch", "heads", "seq", None, mesh=mesh)
        k = constrain(k, rules, "batch", "heads", "seq", None, mesh=mesh)
    else:
        q = _proj(layer, "wq", h, "bte,ed->btd", "bte,er->btr", "btr,rd->btd")
        k = _proj(layer, "wk", h, "bte,ed->btd", "bte,er->btr", "btr,rd->btd")
        v = _proj(layer, "wv", h, "bte,ed->btd", "bte,er->btr", "btr,rd->btd")
        if c.qkv_bias:
            q = q + layer["bq"]
            k = k + layer["bk"]
            v = v + layer["bv"]
        if c.qk_norm_flat:  # OLMo-2: norm the full projection width
            q = rms_norm(q, layer["q_norm"], c.norm_eps)
            k = rms_norm(k, layer["k_norm"], c.norm_eps)
        q = q.reshape(b, t, c.n_heads, c.head_dim).transpose(0, 2, 1, 3)
        k = k.reshape(b, t, c.n_kv_heads, c.head_dim).transpose(0, 2, 1, 3)
        v = v.reshape(b, t, c.n_kv_heads, c.head_dim).transpose(0, 2, 1, 3)
        if c.qk_norm:  # per-head q/k norm before rope (Qwen3/Cohere)
            q, k = qk_norm_apply(q, k, layer, c)
        q = constrain(q, rules, "batch", "heads", "seq", None, mesh=mesh)
        k = constrain(k, rules, "batch", "kv_heads", "seq", None, mesh=mesh)
        if not nope and c.rope_dim:  # partial_rotary 0: no rotary at all
            q = apply_rope(q, cos, sin, interleaved=c.rope_interleaved)
            k = apply_rope(k, cos, sin, interleaved=c.rope_interleaved)
            if c.qk_l2_norm:  # Llama4: weightless L2 norm AFTER rope
                q = l2_norm(q, c.norm_eps)
                k = l2_norm(k, c.norm_eps)
        elif c.attn_temp_scale:
            # Llama4 NoPE layers: position-dependent query temperature
            pos = positions if positions is not None else jnp.arange(t)
            q = q * attn_temp_scales(pos, c)[None, None, :, None].astype(q.dtype)
    # Llama4 blockwise-chunked attention applies on rope layers only
    chunk = 0 if nope else c.attention_chunk_size
    scale = c.attention_scale
    use_sp = mesh is not None and mesh.shape.get("sp", 1) > 1
    if use_sp and chunk:
        raise NotImplementedError(
            "chunked attention (Llama4) does not compose with sp "
            "sequence parallelism yet"
        )
    sinks = layer.get("sinks") if c.attn_sinks else None
    if use_sp and sinks is not None:
        raise NotImplementedError(
            "attention sinks do not compose with sp sequence "
            "parallelism yet (the ring/ulysses paths have no sink "
            "column)"
        )
    if index_mask is not None:
        if use_sp:
            raise NotImplementedError(
                "the sparse indexer does not compose with sp sequence "
                "parallelism yet"
            )
        # the selection is a mask no kernel here takes: dense scores
        sc = jnp.einsum(
            "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
        ) * scale
        pr = jax.nn.softmax(
            jnp.where(index_mask[:, None], sc, -jnp.inf), axis=-1
        )
        o = jnp.einsum("bhqk,bhkd->bhqd", pr.astype(v.dtype), v)
    elif use_sp and c.seq_parallel == "ulysses":
        from dstack_tpu.parallel.ulysses import ulysses_attention

        o = ulysses_attention(
            q, k, v, mesh=mesh, causal=True, scale=scale,
            window=window, softcap=c.attn_softcap,
        )
    elif use_sp:
        o = ring_attention(
            q, k, v, mesh=mesh, causal=True, scale=scale,
            window=window, softcap=c.attn_softcap,
        )
    else:
        o = attention(
            q, k, v, causal=True, scale=scale, impl=attn_impl,
            window=window, softcap=c.attn_softcap, chunk=chunk,
            sinks=sinks, shard=kernel_shard(mesh, rules, b, k.shape[1]),
        )
    if c.mla and c.qk_head_dim > c.v_head_dim:
        o = o[..., : c.v_head_dim]  # drop the zero v padding
    o = head_gate(o, h, layer, c, "bth,bhtv->bhtv")
    o = o.transpose(0, 2, 1, 3).reshape(b, t, c.o_dim)
    out = _proj(layer, "wo", o, "btd,de->bte", "btd,dr->btr", "btr,re->bte")
    if c.proj_bias or c.wo_bias:
        out = out + layer["bo"]
    if c.post_norms:
        out = model_norm(out, layer["attn_post_norm"], c)
    if c.residual_multiplier:  # Granite scales the sublayer output
        out = out * jnp.asarray(c.residual_multiplier, out.dtype)
    return constrain(out, rules, "batch", "seq", None, mesh=mesh)


def _mixer_block(
    x: jax.Array, layer: dict, config: LlamaConfig, mesh: Optional[Mesh],
    rules: ShardingRules, kind: str, m=None,
):
    """A linear, conv or mamba layer's mixer over whole sequences, from a
    past of zeros (models/kda.py, shortconv.py, mamba.py): the training
    and parity path's. A gmu layer (``kind`` ``"gmu"``) gates ``m``, the
    scan output of the mamba layer before it; with ``m`` (a model with
    gmu layers) → (out, the scan output the layers after this one read)."""
    c = config
    h = model_norm(x, layer["attn_norm"], c)
    if kind == "gmu":
        y = gmu_mix(h, m, layer)
    else:
        mixer = mixer_of(kind)
        y = mixer.mix(h, layer, c, *mixer.zeros(c, x.shape[0], x.dtype))[0]
        if kind == "mamba":  # (y, its scan's output)
            y, m = y if m is not None else (y[0], None)
    out = _proj(layer, "wo", y, "btd,de->bte", "btd,dr->btr", "btr,re->bte")
    out = constrain(out, rules, "batch", "seq", None, mesh=mesh)
    return out if m is None else (out, m)


def _mlp_block(
    x: jax.Array,
    layer: dict,
    config: LlamaConfig,
    mesh: Optional[Mesh],
    rules: ShardingRules,
) -> tuple[jax.Array, jax.Array]:
    """Dense SwiGLU or sparse MoE FFN → (out, aux loss scalar).

    The MoE path keys off ``w_router`` *in the layer dict*, not just the
    config: DeepSeek's ``first_k_dense`` prelude layers carry a plain
    dense FFN inside an MoE model and must take the dense branch.
    """
    h = (
        model_norm(x, layer.get("mlp_norm", layer.get("attn_norm")), config)
        if config.pre_norm else x  # OLMo-2 norms the OUTPUT instead
        # (parallel_block shares attn_norm — Cohere's single input norm)
    )
    if config.n_experts and "w_router" in layer:
        from dstack_tpu.models import moe

        o, aux = moe.moe_mlp(
            h,
            layer,
            config.n_experts,
            config.experts_per_token,
            config.capacity_factor,
            mesh,
            rules,
            renorm=config.router_renorm,
            sigmoid_input=config.router_sigmoid_input,
            score=config.router_score,
            groups=config.router_groups,
            routed_scale=config.routed_scale,
            topk_softmax=config.router_topk_softmax,
            act=config.moe_act,
            act_limit=config.act_limit,
            held=config.experts_held,
            zero=config.zero_experts,
        )
        aux_loss = (
            config.router_balance_coef * aux["balance"]
            + config.router_z_coef * aux["z"]
        )
        return o, aux_loss
    u = _proj(layer, "w_up", h, "bte,ef->btf", "bte,er->btr", "btr,rf->btf")
    if config.proj_bias:
        u = u + layer["b_up"]
    if config.mlp_gateless:  # Nemotron: down(act(up(x)))
        # CONFIG-driven branch: int8 quantization renames w_gate to
        # w_gate_q, so key presence would misdetect quantized gated
        # models as gateless
        inner = act_fn(config)(u)
        inner = constrain(inner, rules, "batch", "seq", "mlp", mesh=mesh)
    else:
        g = _proj(layer, "w_gate", h, "bte,ef->btf", "bte,er->btr", "btr,rf->btf")
        g = constrain(g, rules, "batch", "seq", "mlp", mesh=mesh)
        inner = act_fn(config)(g) * u
    o = _proj(
        layer, "w_down", inner, "btf,fe->bte", "btf,fr->btr", "btr,re->bte"
    )
    if config.proj_bias:
        o = o + layer["b_down"]
    if config.post_norms:
        o = model_norm(o, layer["mlp_post_norm"], config)
    if config.residual_multiplier:  # Granite scales the sublayer output
        o = o * jnp.asarray(config.residual_multiplier, o.dtype)
    return constrain(o, rules, "batch", "seq", None, mesh=mesh), jnp.zeros((), jnp.float32)


def expert_branch_of(layer: dict) -> dict:
    """The experts of a layer of several sublayers as an expert layer's
    leaves, under the MLP norm of the first sublayer, whose hidden they
    read."""
    return {
        **{k: v for k, v in layer.items() if k in EXPERT_LEAVES},
        "mlp_norm": layer["sub0"]["mlp_norm"],
    }


def _shortcut_layer(x, layer: dict, n: int, attend, mlp):
    """A layer of ``n`` (attention, dense FFN) pairs with the
    expert branch across them → (x, the branch's aux loss): the experts
    read the hidden that the first dense FFN reads and are added after
    the last one. ``attend(x, sub)`` and ``mlp(x, leaves) -> (out,
    aux)`` are the caller's attention and MLP sublayers."""
    for i in range(n):
        sub = layer[f"sub{i}"]
        x = x + attend(x, sub)
        if i == 0:
            with jax.named_scope("dtpu.scmoe"):
                branch, aux = mlp(x, expert_branch_of(layer))
        x = x + mlp(x, sub)[0]
    return x + branch, aux


def _embed_tokens(
    params: dict,
    tokens: jax.Array,
    config: LlamaConfig,
    mesh: Optional[Mesh],
    rules: ShardingRules,
    positions: Optional[jax.Array],
) -> tuple[jax.Array, tuple, jax.Array]:
    """Shared forward preamble → (x [B,T,H], dual rope pairs, pos)."""
    # Replicate the embed table for the token lookup: a gather from the
    # (vocab-tp, hidden-fsdp)-sharded table would produce hidden-sharded
    # activations that GSPMD can only reshard to batch/seq sharding by
    # full rematerialization (an involuntary-remat warning and an extra
    # copy). An explicit all-gather of the table lets the gather output
    # inherit the token indices' batch/seq sharding directly.
    embed = constrain(params["embed"], rules, None, None, mesh=mesh)
    x = embed.at[tokens].get(mode="fill", fill_value=0).astype(config.dtype)
    if config.embed_scale:
        # Gemma: the normalizer is rounded to the model dtype first
        x = x * jnp.asarray(config.hidden_size**0.5, config.dtype)
    if config.embed_multiplier:
        x = x * jnp.asarray(config.embed_multiplier, config.dtype)
    x = constrain(x, rules, "batch", "seq", None, mesh=mesh)
    pos = positions if positions is not None else jnp.arange(tokens.shape[1])
    return x, dual_rope_freqs(config, pos), pos


def _lm_head(
    params: dict,
    x: jax.Array,  # [B, T, H] final hidden (pre-norm)
    config: LlamaConfig,
    mesh: Optional[Mesh],
    rules: ShardingRules,
    return_hidden: bool,
) -> jax.Array:
    """Shared forward tail: final norm, then logits (or hidden states)."""
    x = model_norm(x, params["final_norm"], config)
    if return_hidden:
        return x
    logits = head_logits_einsum(params, x, config, "bte,ev->btv")
    logits = constrain(logits, rules, "batch", "seq", "vocab", mesh=mesh)
    logits = logits.astype(jnp.float32)
    if config.logit_scale:
        logits = logits * config.logit_scale  # Cohere
    if config.logit_softcap:
        cap = config.logit_softcap
        logits = cap * jnp.tanh(logits / cap)
    return logits


def head_logits_einsum(
    params: dict, x: jax.Array, config: LlamaConfig, eq: str
) -> jax.Array:
    """Output-head matmul (``eq``: "bte,ev->btv" or "be,ev->bv") over
    the tied embedding, the plain ``lm_head``, or its int8 form — the
    per-channel scale multiplies the logits so the int8 bytes are all
    that leaves HBM (models/quant.py)."""
    if config.tie_embeddings:
        head = params["embed"].T
    elif "lm_head" in params:
        head = params["lm_head"]
    else:
        logits = jnp.einsum(
            eq, x, params["lm_head_q"].astype(config.dtype),
            preferred_element_type=jnp.float32,
        )
        return logits * params["lm_head_s"]
    return jnp.einsum(
        eq, x, head.astype(config.dtype), preferred_element_type=jnp.float32
    )


def _merge_lora(xs: dict, lora: Optional[dict], lora_scale: float, config: LlamaConfig) -> dict:
    if lora is None:
        return xs
    return {
        **xs,
        **lora["layers"],
        "lora_scale": jnp.full((config.n_layers,), lora_scale, config.dtype),
    }


def _shared_zeros(c: LlamaConfig, x: jax.Array) -> dict:
    """What layers of a model hand to layers further up at the same
    positions, before any has: ``m`` [B, T, d_inner], the latest mamba
    layer's scan output (the gmu layers read it); ``k`` / ``v``
    [B, Hkv, T, D] at ``attend_config``, the full layer's rows (the
    cross layers read them). Empty for a model with neither."""
    b, t, _ = x.shape
    shared = {}
    if "gmu" in c.layer_types:
        shared["m"] = jnp.zeros((b, t, c.ssm_inner), x.dtype)
    if "cross" in c.layer_types:
        ac = c.attend_config
        shared["k"] = shared["v"] = jnp.zeros(
            (b, ac.n_kv_heads, t, ac.head_dim), x.dtype
        )
    return shared


def _make_shared_run_fn(c: LlamaConfig, run, mesh, rules, attn_impl, ropes, pos):
    """The layer scan's body of one run of a model whose layers hand
    something on (:func:`_shared_zeros`) or attend differentially: carry
    ``(x, shared)``, xs ``(layer, its row among its kind's layers)``."""
    kind = run.kind
    lam_table = (
        diff_lambda_init(c, kind)
        if c.diff_attn and kind in ("full", "window", "cross") else None
    )

    def run_fn(carry, layer_and_row):
        (x, shared), (layer, row) = carry, layer_and_row
        if kind in STATE_KINDS or kind == "gmu":
            ao = _mixer_block(x, layer, c, mesh, rules, kind, shared.get("m"))
            if "m" in shared:
                ao, m = ao
                shared = {**shared, "m": m}
        elif c.diff_attn:
            ao, kv = _diff_attention_block(
                x, layer, run.config, run.window, lam_table[row],
                (shared["k"], shared["v"]) if kind == "cross" else None,
                mesh, rules, attn_impl,
            )
            if kind == "full" and "k" in shared:
                shared = {**shared, "k": kv[0], "v": kv[1]}
        else:  # plain attention beside mamba and gmu layers
            cos, sin = layer_rope(ropes, c, run.window)
            ao = _attention_block(
                x, layer, run.config, cos, sin, mesh, rules, attn_impl,
                window=run.window, positions=pos,
            )
        x = x + ao
        o, aux = _mlp_block(x, layer, c, mesh, rules)
        return (x + o, shared), aux

    if c.remat:
        run_fn = jax.checkpoint(
            run_fn,
            policy=jax.checkpoint_policies.save_only_these_names("flash_residuals"),
        )
    return run_fn


def forward(
    params: dict,
    tokens: jax.Array,  # [B, T] int32
    config: LlamaConfig,
    mesh: Optional[Mesh] = None,
    rules: Optional[ShardingRules] = None,
    attn_impl: Optional[str] = None,
    positions: Optional[jax.Array] = None,
    lora: Optional[dict] = None,
    lora_scale: float = 1.0,
    return_hidden: bool = False,
    return_aux: bool = False,
) -> jax.Array:
    """Token ids → logits [B, T, vocab] (f32).

    With ``return_hidden=True`` returns the final normed hidden states
    [B, T, hidden] (model dtype) instead — callers then apply the LM
    head themselves (train/step.py fuses it into the loss so full-vocab
    log-probabilities never hit HBM; see fused_cross_entropy /
    chunked_cross_entropy there).

    With ``return_aux=True`` returns ``(out, aux)`` where ``aux`` is the
    summed router auxiliary loss (MoE configs; 0.0 for dense).

    ``lora`` is an adapter pytree from train/lora.py: stacked per-layer
    low-rank factors scanned together with the base weights — the
    adapters ride the same lax.scan, so XLA sees one fused layer body.
    """
    c = config
    rules = rules or default_rules()
    x, ropes, pos = _embed_tokens(params, tokens, c, mesh, rules, positions)

    def make_group_fn(wins: tuple, nps: tuple, stacked: bool, ac=c, kind="full"):
        # ``ac``: the attention shape of the layers scanned (a group's),
        # ``kind``: what mixes their tokens (a group's: ``LayerRun.kind``)
        def group_fn(x, group):
            aux = jnp.zeros((), jnp.float32)
            for i, (w, np_) in enumerate(zip(wins, nps)):
                layer = (
                    jax.tree.map(lambda a: a[i], group) if stacked else group
                )
                cos, sin = layer_rope(ropes, c, w)
                attend = functools.partial(
                    _attention_block, config=ac, cos=cos, sin=sin, mesh=mesh,
                    rules=rules, attn_impl=attn_impl, window=w, nope=np_,
                    positions=pos,
                )
                if kind in STATE_KINDS:  # a mixer in the attention's place
                    attend = functools.partial(
                        _mixer_block, config=c, mesh=mesh, rules=rules, kind=kind
                    )
                if c.sublayers > 1:
                    x, aux_i = _shortcut_layer(
                        x, layer, c.sublayers, attend,
                        functools.partial(
                            _mlp_block, config=c, mesh=mesh, rules=rules
                        ),
                    )
                    aux = aux + aux_i
                    continue
                ao = attend(x, layer)
                if c.parallel_block:
                    # Cohere: attention and MLP read the SAME input,
                    # outputs add jointly (mlp_norm aliases attn_norm)
                    o, aux_i = _mlp_block(x, layer, c, mesh, rules)
                    x = x + ao + o
                else:
                    x = x + ao
                    o, aux_i = _mlp_block(x, layer, c, mesh, rules)
                    x = x + o
                aux = aux + aux_i
            return x, aux

        if c.remat:
            # Save the flash-attention residuals (q/k/v/o/lse, tagged
            # in ops/flash.py) across the remat boundary: the backward
            # pass then reuses them instead of re-running the attention
            # kernel, at ~80MB/layer — everything else is recomputed.
            group_fn = jax.checkpoint(
                group_fn,
                policy=jax.checkpoint_policies.save_only_these_names(
                    "flash_residuals"
                ),
            )
        return group_fn

    if c.layer_types:
        # layer groups (two attention shapes): each run of consecutive
        # layers of one kind scans its slice of its group's stack
        if lora is not None:
            raise NotImplementedError("LoRA over layer groups")
        aux = jnp.zeros((), jnp.float32)
        shared = _shared_zeros(c, x)
        for run in layer_runs(c):
            if shared or c.diff_attn:
                (x, shared), auxs = jax.lax.scan(
                    _make_shared_run_fn(c, run, mesh, rules, attn_impl, ropes, pos),
                    (x, shared), (
                        run_slice(params[run.key], run),
                        run_row(c, run) + jnp.arange(run.hi - run.lo),
                    ),
                )
            else:
                x, auxs = jax.lax.scan(
                    make_group_fn((run.window,), (False,), False, run.config, run.kind),
                    x, run_slice(params[run.key], run),
                )
            aux = aux + jnp.sum(auxs)
        out = _lm_head(params, x, c, mesh, rules, return_hidden)
        return (out, aux) if return_aux else out
    # mixed per-layer attention (Gemma2/3 sliding windows, Llama4 NoPE)
    # scans in groups of `g` sublayers so every window/rope choice is
    # static — the flash kernel stays usable (a traced window would
    # force the masked XLA path)
    if lora is not None and c.sublayers > 1:
        raise NotImplementedError("LoRA over layers of several sublayers")
    xs = _merge_lora(params["layers"], lora, lora_scale, c)
    g, windows, xs_main, xs_tail = grouped_scan_layout(c, xs)
    nopes = layer_nope(c)
    if "dense_layers" in params:
        # DeepSeek first-k dense prelude: same attention, plain FFN,
        # scanned before the MoE stack (uniform attention — no family
        # mixes first_k_dense with sliding windows or NoPE)
        x, _ = jax.lax.scan(
            make_group_fn((windows[0],), (nopes[0],), False),
            x,
            params["dense_layers"],
        )
    x, auxs = jax.lax.scan(
        make_group_fn(tuple(windows[:g]), tuple(nopes[:g]), g > 1), x, xs_main
    )
    aux = jnp.sum(auxs)
    if xs_tail is not None:
        # pattern doesn't divide the layer count (Gemma3): the last
        # L % g layers run unrolled after the scan
        r = c.n_layers % g
        x, aux_tail = make_group_fn(
            tuple(windows[-r:]), tuple(nopes[-r:]), True
        )(x, xs_tail)
        aux = aux + aux_tail
    out = _lm_head(params, x, c, mesh, rules, return_hidden)
    return (out, aux) if return_aux else out


def forward_pipelined(
    params: dict,
    tokens: jax.Array,  # [B, T] int32
    config: LlamaConfig,
    *,
    mesh: Mesh,
    rules: Optional[ShardingRules] = None,
    n_micro: Optional[int] = None,
    attn_impl: Optional[str] = None,
    positions: Optional[jax.Array] = None,
    lora: Optional[dict] = None,
    lora_scale: float = 1.0,
    return_hidden: bool = False,
    return_aux: bool = False,
) -> jax.Array:
    """:func:`forward` with the layer stack pipelined over the ``pp``
    mesh axis (parallel/pipeline.py): layers split into contiguous
    stages, batch split into ``n_micro`` microbatches, activations
    ppermute between neighbor stages. Embed/rope/head run pp-replicated
    (GSPMD still shards them over tp/fsdp); ring attention (``sp``)
    cannot nest inside the pipeline's shard_map, so pp meshes use local
    attention per device.
    """
    from dstack_tpu.parallel import pipeline as pl

    c = config
    rules = rules or default_rules()
    pp = mesh.shape.get("pp", 1)
    if c.n_layers % pp != 0:
        raise ValueError(f"{c.n_layers} layers not divisible by pp={pp}")
    windows = layer_windows(c)
    if len(set(windows)) > 1:
        raise ValueError(
            "forward_pipelined supports a uniform attention window only "
            "(mixed sliding/global layers don't split into equal stages)"
        )
    if any(layer_nope(c)) or c.attention_chunk_size:
        raise ValueError(
            "forward_pipelined does not support Llama4 NoPE/chunked "
            "layers (mixed layer kinds don't split into equal stages)"
        )
    if c.first_k_dense:
        raise ValueError(
            "forward_pipelined does not support DeepSeek first_k_dense "
            "prelude layers (mixed layer kinds don't split into equal "
            "stages)"
        )
    window = windows[0]
    n_micro = n_micro or pp
    x, ropes, _pos = _embed_tokens(params, tokens, c, mesh, rules, positions)
    cos, sin = layer_rope(ropes, c, window)

    def stage_fn(stage_layers, x, extras):
        cos, sin = extras

        def body(x, layer):
            # mesh=None inside the stage: GSPMD propagates the auto-axis
            # (fsdp/tp/ep) shardings; explicit constraints can't name the
            # concrete mesh from inside the pp shard_map
            x = x + _attention_block(
                x, layer, c, cos, sin, None, rules, attn_impl, window=window
            )
            o, aux = _mlp_block(x, layer, c, None, rules)
            return x + o, aux

        if c.remat:
            body = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.save_only_these_names(
                    "flash_residuals"
                ),
            )
        y, auxs = jax.lax.scan(body, x, stage_layers)
        return y, jnp.sum(auxs).astype(jnp.float32)

    xs = _merge_lora(params["layers"], lora, lora_scale, c)
    stage_params = pl.split_stages(xs, pp)
    x_mb = pl.microbatch(x, n_micro)
    # microbatch dim replicated, per-microbatch batch dim sharded over the
    # batch axes: keeps the boundary reshapes local (see pl.microbatch)
    x_mb = constrain(x_mb, rules, None, "batch", "seq", None, mesh=mesh)
    y_mb, aux = pl.pipeline_apply(
        stage_fn, stage_params, x_mb, mesh=mesh, extras=(cos, sin)
    )
    y_mb = constrain(y_mb, rules, None, "batch", "seq", None, mesh=mesh)
    x = pl.unmicrobatch(y_mb)
    out = _lm_head(params, x, c, mesh, rules, return_hidden)
    return (out, aux) if return_aux else out


def abstract_params(config: LlamaConfig) -> dict:
    """Shape/dtype tree without allocating (for sharding planning)."""
    return jax.eval_shape(lambda: init_params(config, jax.random.key(0)))
