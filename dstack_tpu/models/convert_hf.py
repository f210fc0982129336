"""HuggingFace checkpoint → dstack_tpu parameter pytree.

Bridges the serving/fine-tune paths to real released weights: point
``load_checkpoint`` at a ``save_pretrained`` directory (safetensors or
torch ``.bin`` shards) and get back ``(LlamaConfig, params)`` ready for
:func:`dstack_tpu.models.llama.forward`, the serve engine, and the
finetune driver.

Supported ``model_type``s: ``llama``, ``qwen2``, ``qwen3``,
``qwen3_moe``, ``mistral``, ``gemma``, ``gemma2``, ``lfm2`` (and
``lfm2_moe``'s config keys), ``gemma3``/
``gemma3_text`` (multimodal checkpoints load their text tower),
``mixtral``, ``phi3`` (fused qkv/gate_up projections are split on
load; a Phi-3 export round-trips as the equivalent mistral/llama
layout), ``gpt_oss`` (attention sinks, linear router with
softmax-over-top-k gates, fused biased experts with the clamped glu,
yarn truncate=false). Each maps onto :class:`LlamaConfig` family flags (qkv_bias /
sliding_window / norm_offset / softcaps / dual-theta rope / MoE) — the
architecture deltas live in the config, not in per-family model code.

The reference framework never loads weights itself (user containers do);
this module is part of the in-repo inference/training engine that makes
``type: service`` self-contained.

Layout notes:
- HF ``*_proj.weight`` is [out, in] (torch Linear); our kernels want
  [in, out] → transpose.
- HF llama-family checkpoints already use the rotate-half RoPE
  convention (no head permutation needed, unlike Meta's originals).
- Our layer stacks are scanned: every per-layer leaf gains a leading
  ``[n_layers, ...]`` dim.
"""

import json
import math
from pathlib import Path
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dstack_tpu.models.llama import LlamaConfig
from dstack_tpu.models.llama import layer_windows as _layer_windows

__all__ = [
    "config_from_hf",
    "config_to_hf",
    "convert_state_dict",
    "export_state_dict",
    "load_checkpoint",
    "save_checkpoint",
]


def config_from_hf(hf: dict, dtype: Any = jnp.bfloat16) -> LlamaConfig:
    """HF ``config.json`` dict → :class:`LlamaConfig`."""
    mt = hf.get("model_type", "llama")
    if mt in ("gemma3", "llama4") and "text_config" in hf:
        # multimodal wrapper: the text tower's config is nested (the
        # vision tower is out of scope; load_checkpoint strips its
        # weights and the language_model prefix)
        hf = {**hf["text_config"], "model_type": f"{mt}_text"}
        mt = f"{mt}_text"
    if mt in ("lfm2", "lfm2_moe"):
        return _lfm2_config(hf, dtype, mt)
    hidden = hf["hidden_size"]
    n_heads = hf["num_attention_heads"]
    head_dim = hf.get("head_dim") or hidden // n_heads
    if hf.get("attention_bias") and mt not in (
        "qwen2", "qwen3", "qwen3_moe", "glm", "glm4", "gpt_oss"
    ):
        # q/k/v/o biases exist in the checkpoint but our llama/mistral
        # paths would silently drop them — refuse rather than mis-serve
        # (StarCoder2 spells its biases use_bias, handled in its branch)
        raise ValueError(
            f"{mt} checkpoint sets attention_bias=true, which this "
            "converter only supports for qwen2/qwen3/glm/glm4"
        )
    act = hf.get("hidden_act") or "silu"
    act_map = {
        "silu": "silu", "gelu_pytorch_tanh": "gelu_tanh", "relu2": "relu2"
    }
    if mt in ("gemma", "gemma2", "gemma3", "gemma3_text"):
        # Gemma configs historically say "gelu"/hidden_activation but
        # the models always use the tanh approximation
        act = "gelu_tanh"
    elif act not in act_map:
        raise ValueError(
            f"unsupported hidden_act {act!r} (supported: {sorted(act_map)})"
        )
    else:
        act = act_map[act]
    common = dict(
        hidden_act=act,
        vocab_size=hf["vocab_size"],
        hidden_size=hidden,
        n_layers=hf["num_hidden_layers"],
        n_heads=n_heads,
        n_kv_heads=hf.get("num_key_value_heads", n_heads),
        head_dim=head_dim,
        intermediate_size=hf["intermediate_size"],
        rope_theta=hf.get("rope_theta", 10000.0),
        norm_eps=hf.get("rms_norm_eps", 1e-6),
        max_seq_len=hf.get("max_position_embeddings", 8192),
        tie_embeddings=hf.get("tie_word_embeddings", False),
        rope_scaling=_rope_scaling_from_hf(hf),
        dtype=dtype,
    )
    if mt == "llama":
        return LlamaConfig(**common)
    if mt == "qwen2":
        if hf.get("use_sliding_window"):
            # HF Qwen2 windows only layers >= max_window_layers — a
            # layering our periodic sliding_pattern can't express except
            # uniformly; refuse rather than silently run full attention
            if hf.get("max_window_layers", 0) not in (0, None):
                raise ValueError(
                    "qwen2 use_sliding_window with max_window_layers > 0 "
                    "is not supported"
                )
            common["sliding_window"] = hf.get("sliding_window") or 0
        # Qwen2 puts biases on q/k/v only (attention_bias is not in its
        # config; the arch always has them)
        return LlamaConfig(**common, qkv_bias=True)
    if mt == "qwen3":
        lt = hf.get("layer_types") or []
        if hf.get("use_sliding_window") or "sliding_attention" in lt:
            raise ValueError(
                "qwen3 sliding-attention layer_types are not supported"
            )
        return LlamaConfig(
            **common, qk_norm=True,
            qkv_bias=bool(hf.get("attention_bias")),
        )
    if mt == "qwen3_moe":
        # qwen3 attention (qk-norm) + sparse MoE MLP on every layer.
        # Checkpoints mixing dense and sparse layers can't be expressed
        # by the uniform layer stack — refuse rather than mis-run.
        if hf.get("mlp_only_layers") or hf.get("decoder_sparse_step", 1) != 1:
            raise ValueError(
                "qwen3_moe with dense layers (mlp_only_layers / "
                "decoder_sparse_step != 1) is not supported"
            )
        if hf.get("use_sliding_window"):
            raise ValueError("qwen3_moe sliding windows are not supported")
        common["intermediate_size"] = hf["moe_intermediate_size"]
        return LlamaConfig(
            **common,
            qk_norm=True,
            qkv_bias=bool(hf.get("attention_bias")),
            n_experts=hf["num_experts"],
            experts_per_token=hf.get("num_experts_per_tok", 8),
            router_renorm=bool(hf.get("norm_topk_prob", True)),
        )
    if mt == "gpt_oss":
        # OpenAI gpt-oss: alternating sliding/full attention with
        # learned attention sinks, a LINEAR router (bias + softmax over
        # the top-k logits), fused biased experts with the clamped glu
        # activation, yarn rope with truncate=false (HF
        # modeling_gpt_oss.py is the parity reference).
        lt = hf.get("layer_types") or []
        expected = [
            "sliding_attention" if i % 2 == 0 else "full_attention"
            for i in range(hf["num_hidden_layers"])
        ]
        if lt and lt != expected:
            raise ValueError(
                "gpt_oss layer_types deviate from the alternating "
                "sliding/full pattern; not supported"
            )
        return LlamaConfig(
            **common,
            qkv_bias=True,
            proj_bias=True,  # o-proj bias (dense-MLP biases N/A: MoE)
            attn_sinks=True,
            sliding_window=hf.get("sliding_window") or 0,
            # absent layer_types default to the alternating pattern in
            # HF GptOssConfig — a 0 fallback would window EVERY layer
            sliding_pattern=2,
            n_experts=hf["num_local_experts"],
            experts_per_token=hf.get("num_experts_per_tok", 4),
            router_topk_softmax=True,
            moe_bias=True,
            moe_act="oai_glu",
            act_limit=float(hf.get("swiglu_limit") or 7.0),
        )
    if mt == "mistral":
        return LlamaConfig(**common, sliding_window=hf.get("sliding_window") or 0)
    if mt == "phi3":
        if float(hf.get("partial_rotary_factor") or 1.0) != 1.0:
            raise ValueError("phi3 partial_rotary_factor != 1 is not supported")
        return LlamaConfig(**common, sliding_window=hf.get("sliding_window") or 0)
    if mt == "gemma":
        return LlamaConfig(
            **{**common, "tie_embeddings": True},
            norm_offset=True,
            embed_scale=True,
        )
    if mt == "gemma2":
        return LlamaConfig(
            **{**common, "tie_embeddings": True},
            norm_offset=True,
            embed_scale=True,
            post_norms=True,
            sliding_window=hf.get("sliding_window") or 0,
            sliding_pattern=2,  # even layers sliding, odd global
            attn_softcap=hf.get("attn_logit_softcapping") or 0.0,
            logit_softcap=hf.get("final_logit_softcapping") or 0.0,
            attn_scale=float(hf["query_pre_attn_scalar"]) ** -0.5
            if hf.get("query_pre_attn_scalar")
            else None,
        )
    if mt == "mixtral":
        return LlamaConfig(
            **common,
            n_experts=hf["num_local_experts"],
            experts_per_token=hf.get("num_experts_per_tok", 2),
            router_renorm=True,
        )
    if mt in ("gemma3", "gemma3_text"):
        sw = hf.get("sliding_window") or 0
        sw, pattern = _gemma3_pattern(hf, sw)
        return LlamaConfig(
            **{**common, "tie_embeddings": hf.get("tie_word_embeddings", True)},
            norm_offset=True,
            embed_scale=True,
            post_norms=True,
            qk_norm=True,
            sliding_window=sw,
            sliding_pattern=pattern,
            # dual rope: sliding layers rotate at the unscaled local
            # theta, global layers at rope_theta (+ linear scaling)
            rope_local_theta=hf.get("rope_local_base_freq", 10000.0),
            attn_scale=float(hf["query_pre_attn_scalar"]) ** -0.5
            if hf.get("query_pre_attn_scalar")
            else None,
        )
    if mt in ("llama4", "llama4_text"):
        return _llama4_config(hf, common)
    if mt in ("deepseek_v2", "deepseek_v3"):
        return _deepseek_config(hf, common, mt)
    if mt == "granite":
        # IBM Granite: llama skeleton + four scalar multipliers
        # (attention_multiplier IS the softmax scale; logits_scaling
        # divides, so it maps onto 1/logit_scale)
        ls = float(hf.get("logits_scaling") or 1.0)
        return LlamaConfig(
            **common,
            qkv_bias=False,
            attn_scale=float(hf.get("attention_multiplier") or 1.0),
            embed_multiplier=float(hf.get("embedding_multiplier") or 1.0),
            residual_multiplier=float(hf.get("residual_multiplier") or 1.0),
            logit_scale=(1.0 / ls) if ls != 1.0 else 0.0,
        )
    if mt == "starcoder2":
        # StarCoder2: plain LayerNorm with bias (stacked storage),
        # biases on every projection, gateless GELU MLP (c_fc/c_proj),
        # full-width rotate-half rope, tied embeddings
        return LlamaConfig(
            **{**common,
               "norm_eps": float(hf.get("norm_epsilon", 1e-5)),
               "tie_embeddings": bool(hf.get("tie_word_embeddings", True)),
               "sliding_window": hf.get("sliding_window") or 0},
            norm_type="layernorm_bias",
            mlp_gateless=True,
            qkv_bias=bool(hf.get("use_bias", True)),
            proj_bias=bool(hf.get("use_bias", True)),
        )
    if mt == "nemotron":
        # Nemotron/Minitron: LayerNorm1P ((1+w)·norm + b, stored stacked
        # [2, H]), gateless relu² MLP, rotate-half partial rotary
        return LlamaConfig(
            **{**common, "norm_eps": float(hf.get("norm_eps", 1e-5))},
            norm_type="layernorm1p",
            mlp_gateless=True,
            partial_rotary=float(hf.get("partial_rotary_factor") or 0.5),
        )
    if mt == "cohere":
        # Command-R: mean-centered LayerNorm, parallel attn+MLP block
        # over ONE shared input norm, interleaved rope, logit_scale,
        # optional per-head qk LayerNorm, tied embeddings
        return LlamaConfig(
            **{**common,
               "norm_eps": float(hf.get("layer_norm_eps", 1e-5)),
               # Cohere ties by default and omits the key when tied
               "tie_embeddings": bool(hf.get("tie_word_embeddings", True))},
            norm_type="layernorm",
            parallel_block=True,
            rope_interleaved=True,
            qk_norm=bool(hf.get("use_qk_norm")),
            logit_scale=float(hf.get("logit_scale", 0.0625)),  # HF default
        )
    if mt == "cohere2":
        # Command R7B: the Cohere layout (LayerNorm, parallel block,
        # logit_scale, interleaved rope) + a periodic sliding layout
        # where the full-attention layers carry NO rope at all — the
        # NoPE layers ARE the global layers, same period
        if hf.get("use_qk_norm"):
            raise ValueError("cohere2 use_qk_norm is not supported")
        # cohere2's default period is 4 (_gemma3_pattern would fall
        # back to Gemma3's 6 when both layout fields are absent)
        hf_l = {**hf}
        hf_l.setdefault("sliding_window_pattern", 4)
        sw, pattern = _gemma3_pattern(hf_l, hf.get("sliding_window") or 0)
        return LlamaConfig(
            **{**common,
               "norm_eps": float(hf.get("layer_norm_eps", 1e-5)),
               "tie_embeddings": bool(hf.get("tie_word_embeddings", True))},
            norm_type="layernorm",
            parallel_block=True,
            rope_interleaved=True,
            logit_scale=float(hf.get("logit_scale", 0.0625)),
            sliding_window=sw,
            sliding_pattern=pattern,
            nope_pattern=pattern if sw else 0,
        )
    if mt == "olmo2":
        # OLMo-2: NO pre-norms (sublayer outputs are normed), q/k
        # RMSNorm over the full projection width before head reshape
        return LlamaConfig(
            **common, pre_norm=False, post_norms=True, qk_norm_flat=True
        )
    if mt in ("glm", "glm4"):
        # GLM-4: partial rotary (interleaved, first half of head_dim),
        # qkv bias, fused gate_up MLP (split on load); glm4 adds
        # Gemma2-style sandwich norms (post_self_attn/post_mlp)
        return LlamaConfig(
            **common,
            # GLM defaults attention_bias=True but it is a real config
            # knob — honor bias-free checkpoints
            qkv_bias=bool(hf.get("attention_bias", True)),
            rope_interleaved=True,
            partial_rotary=float(hf.get("partial_rotary_factor") or 0.5),
            post_norms=(mt == "glm4"),
        )
    raise ValueError(f"unsupported HF model_type {mt!r}")


def _v2_mscale_fix() -> bool:
    """Opt-in: scale DeepSeek-V2 attention like the released model's
    remote-code modeling (mscale^2 correction) instead of HF's native
    DeepseekV2Attention. See the comment at the use site."""
    import os

    return os.environ.get("DTPU_DEEPSEEK_V2_MSCALE_FIX", "").lower() in (
        "1", "true", "yes"
    )


def _deepseek_config(hf: dict, common: dict, mt: str) -> LlamaConfig:
    """DeepSeek-V2/V3 → LlamaConfig: MLA attention (latent kv, split
    nope/rope head dims, own v dim), dense-prelude + fine-grained MoE
    with shared experts; V3 adds sigmoid scoring with a selection-only
    correction bias and group-limited top-k."""
    if hf.get("attention_bias"):
        raise ValueError(f"{mt} attention_bias=true is not supported")
    v3 = mt == "deepseek_v3"
    mla = dict(
        q_lora_rank=hf.get("q_lora_rank") or 0,
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"],
    )
    rs = hf.get("rope_scaling")
    if rs and rs.get("mscale_all_dim") and (v3 or _v2_mscale_fix()):
        # HF DeepseekV3Attention multiplies the softmax scale by
        # yarn mscale(factor, mscale_all_dim)^2 — and HF's native
        # DeepseekV2Attention does NOT (verified against transformers
        # 4.57.6), while DeepSeek's original remote-code V2 modeling
        # DOES. V2-Lite ships mscale_all_dim=0.707, so the two versions
        # disagree by ~1.59x on the intended attention scale. Default
        # follows HF (so parity tests against HF outputs pass);
        # DTPU_DEEPSEEK_V2_MSCALE_FIX=1 opts V2 into the released
        # model's intended scale (the remote-code behavior). V3 always
        # applies it — both implementations agree there.
        ms = 0.1 * float(rs["mscale_all_dim"]) * math.log(float(rs["factor"])) + 1.0
        qk_dim = hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]
        mla["attn_scale"] = qk_dim**-0.5 * ms * ms
    n_routed = hf.get("n_routed_experts")
    n_layers = hf["num_hidden_layers"]
    first_k = hf.get("first_k_dense_replace", 0)
    if not n_routed or first_k >= n_layers:
        # every layer dense: a plain MLA transformer
        return LlamaConfig(**common, **mla)
    if hf.get("moe_layer_freq", 1) != 1:
        raise ValueError(f"{mt} moe_layer_freq != 1 is not supported")
    topk_method = hf.get("topk_method") or ("noaux_tc" if v3 else "greedy")
    if topk_method == "group_limited_greedy" or v3:
        groups = (hf["n_group"], hf["topk_group"])
        if groups == (1, 1):
            groups = ()  # one group of everything = no limiting
    elif topk_method == "greedy":
        groups = ()
    else:
        raise ValueError(f"{mt} topk_method {topk_method!r} is not supported")
    shared = hf.get("n_shared_experts") or 0
    moe_inter = hf["moe_intermediate_size"]
    common = {**common, "intermediate_size": moe_inter}
    return LlamaConfig(
        **common,
        **mla,
        n_experts=n_routed,
        experts_per_token=hf["num_experts_per_tok"],
        router_renorm=bool(hf.get("norm_topk_prob", False)),
        router_score="sigmoid" if v3 else "softmax",
        router_bias=v3,  # e_score_correction_bias (noaux_tc)
        router_groups=groups,
        routed_scale=float(hf.get("routed_scaling_factor", 1.0)),
        moe_shared_expert=shared > 0,
        moe_shared_intermediate=shared * moe_inter,
        first_k_dense=first_k,
        dense_intermediate=hf["intermediate_size"],
    )


def _lfm2_config(hf: dict, dtype: Any, mt: str) -> LlamaConfig:
    """LFM2 (``lfm2``, and ``lfm2_moe``'s config keys) → LlamaConfig:
    gated short-convolution layers (``layer_types`` kind ``conv``,
    models/shortconv.py) beside grouped-query layers with a per-head
    q/k norm before a half-split rope; ``lfm2`` a dense SwiGLU on every
    layer (HF ``Lfm2MLP``: its width adjusted as there), ``lfm2_moe``
    ``num_dense_layers`` dense layers and then sigmoid-routed experts
    with a selection-only bias (``use_expert_bias``), gates normed over
    the picks (``norm_topk_prob``)."""
    if hf.get("conv_bias"):
        raise ValueError(f"{mt} conv_bias=true is not supported")
    n_layers = hf["num_hidden_layers"]
    kinds = hf.get("layer_types") or [
        "full_attention"
        if i in (hf.get("full_attn_idxs") or range(n_layers)) else "conv"
        for i in range(n_layers)
    ]
    if not set(kinds) <= {"full_attention", "conv"}:
        raise ValueError(f"{mt} layer_types {sorted(set(kinds))} are not supported")
    inter = hf.get("block_ff_dim", hf["intermediate_size"])
    if mt == "lfm2" and hf.get("block_auto_adjust_ff_dim", True):
        inter = int(2 * inter / 3)
        if hf.get("block_ffn_dim_multiplier", 1.0) is not None:
            inter = int(hf.get("block_ffn_dim_multiplier", 1.0) * inter)
            of = hf.get("block_multiple_of", 256)
            inter = of * ((inter + of - 1) // of)
    rope = hf.get("rope_parameters") or {}
    common = dict(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        n_layers=n_layers,
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        head_dim=hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"],
        intermediate_size=inter,
        rope_theta=float(hf.get("theta") or hf.get("rope_theta") or rope.get("rope_theta", 1e6)),
        norm_eps=hf.get("norm_eps", 1e-5),
        max_seq_len=hf.get("max_position_embeddings", 128000),
        tie_embeddings=hf.get("tie_embedding", hf.get("tie_word_embeddings", True)),
        qk_norm=True,
        layer_types=tuple("full" if k == "full_attention" else "conv" for k in kinds),
        conv_taps=hf.get("conv_L_cache", 3),
        dtype=dtype,
    )
    if mt == "lfm2":
        return LlamaConfig(**common)
    k_dense = hf.get("num_dense_layers", 0)
    n, k = hf["num_experts"], hf["num_experts_per_tok"]
    return LlamaConfig(
        **{**common, "intermediate_size": hf["moe_intermediate_size"]},
        first_k_dense=k_dense,
        dense_intermediate=inter,
        n_experts=n,
        experts_per_token=k,
        capacity_factor=n / k,  # dropless
        router_score="sigmoid",
        router_bias=bool(hf.get("use_expert_bias", False)),
        router_renorm=bool(hf.get("norm_topk_prob", True)),
        routed_scale=float(hf.get("routed_scaling_factor", 1.0)),
    )


def _llama4_config(hf: dict, common: dict) -> LlamaConfig:
    """Llama4 text tower → LlamaConfig (interleaved rope, periodic NoPE
    layers, chunked attention, qk L2 norm, temperature tuning,
    sigmoid-input-scaled MoE with a shared expert)."""
    n_layers = hf["num_hidden_layers"]
    # every layer must be MoE: the uniform layer stack can't express
    # Maverick's interleaved dense/MoE layers
    step = hf.get("interleave_moe_layer_step", 1)
    moe_layers = hf.get("moe_layers")
    if step != 1 or (moe_layers is not None and len(moe_layers) != n_layers):
        raise ValueError(
            "llama4 with interleaved dense/MoE layers "
            "(interleave_moe_layer_step != 1) is not supported"
        )
    # no_rope_layers: 1 = rope, 0 = NoPE; expect the periodic
    # every-p-th-layer-NoPE layout
    nrl = hf.get("no_rope_layers")
    if nrl:
        nope_ix = [i for i, use_rope in enumerate(nrl) if not use_rope]
        if not nope_ix:
            pattern = 0
        else:
            pattern = nope_ix[0] + 1
            expect = [0 if (i + 1) % pattern == 0 else 1 for i in range(n_layers)]
            if [1 if r else 0 for r in nrl] != expect:
                raise ValueError(
                    f"llama4 no_rope_layers {nrl!r} is not the periodic "
                    f"1-NoPE-per-{pattern} layout this stack expresses"
                )
    else:
        pattern = 4
    return LlamaConfig(
        **common,
        rope_interleaved=True,
        nope_pattern=pattern,
        attention_chunk_size=hf.get("attention_chunk_size") or 0,
        qk_l2_norm=bool(hf.get("use_qk_norm", True)),
        attn_temp_scale=(
            float(hf.get("attn_scale", 0.1))
            if hf.get("attn_temperature_tuning") else 0.0
        ),
        attn_temp_floor=float(hf.get("floor_scale", 8192.0)),
        n_experts=hf["num_local_experts"],
        experts_per_token=hf.get("num_experts_per_tok", 1),
        router_sigmoid_input=True,
        moe_shared_expert=True,
    )


def _gemma3_pattern(hf: dict, sliding_window: int) -> tuple[int, int]:
    """Gemma3 layer layout → (sliding_window, sliding_pattern).

    Newer HF configs spell the layout as an explicit ``layer_types``
    list; older ones as ``sliding_window_pattern`` (every p-th layer
    global). Only the periodic layouts our stack expresses are
    accepted — an aperiodic list is a hard error, not silent full
    attention. When no layer actually slides, the window is zeroed
    too: (sw, pattern=0) with sw > 0 would mean "uniform sliding" to
    :func:`~dstack_tpu.models.llama.layer_windows`."""
    lt = hf.get("layer_types")
    if lt:
        if not sliding_window or "sliding_attention" not in lt:
            return 0, 0  # all-global layout: no window anywhere
        globals_ix = [i for i, t in enumerate(lt) if t == "full_attention"]
        if not globals_ix:
            return sliding_window, 0  # uniform sliding (n_layers < pattern)
        p = globals_ix[0] + 1
        expect = [
            "full_attention" if (i + 1) % p == 0 else "sliding_attention"
            for i in range(len(lt))
        ]
        if lt != expect:
            raise ValueError(
                f"gemma3 layer_types {lt!r} is not the periodic "
                f"1-global-per-{p} layout this stack expresses"
            )
        return sliding_window, p
    return sliding_window, int(hf.get("sliding_window_pattern") or 6)


# MoE tensor naming per family: (router weight, expert prefix,
# (gate, up, down) per-expert names) — ONE table consumed by both
# convert_state_dict and export_state_dict so import/export round-trip
# symmetry can't drift.
_MOE_NAMES = {
    "qwen3_moe": (
        "mlp.gate.weight", "mlp.experts",
        ("gate_proj", "up_proj", "down_proj"),
    ),
    "mixtral": (
        "block_sparse_moe.gate.weight", "block_sparse_moe.experts",
        ("w1", "w3", "w2"),
    ),
}


def _rope_scaling_from_hf(hf: dict) -> Optional[tuple]:
    """HF ``rope_scaling`` → :class:`LlamaConfig` tuple (llama3 only).

    Llama-3.1/3.2 checkpoints rescale rope frequencies; ignoring the
    field would load without error but generate silently-degraded text,
    so unknown scaling types are a hard error.
    """
    rs = hf.get("rope_scaling")
    if not rs:
        return None
    rope_type = rs.get("rope_type") or rs.get("type")
    if rope_type in (None, "default"):
        return None
    if rope_type == "llama3":
        return (
            float(rs["factor"]),
            float(rs["low_freq_factor"]),
            float(rs["high_freq_factor"]),
            float(rs["original_max_position_embeddings"]),
        )
    if rope_type == "linear":
        # classic position interpolation (Gemma3 global layers):
        # every frequency divided by the factor
        return ("linear", float(rs["factor"]))
    if rope_type == "yarn":
        # NTK-by-parts YaRN (DeepSeek): mirror HF's
        # _compute_yarn_parameters, resolving the cos/sin attention
        # factor from mscale/mscale_all_dim at conversion time
        truncate = bool(rs.get("truncate", True))
        factor = float(rs["factor"])

        def get_mscale(scale, ms=1.0):
            return 1.0 if scale <= 1 else 0.1 * ms * math.log(scale) + 1.0

        att = rs.get("attention_factor")
        if att is None:
            mscale = rs.get("mscale")
            mscale_all = rs.get("mscale_all_dim")
            if mscale and mscale_all:
                att = get_mscale(factor, mscale) / get_mscale(factor, mscale_all)
            else:
                att = get_mscale(factor)
        orig = (
            rs.get("original_max_position_embeddings")
            or hf.get("max_position_embeddings", 8192)
        )
        return (
            "yarn", factor,
            float(rs.get("beta_fast") or 32),
            float(rs.get("beta_slow") or 1),
            float(orig), float(att),
            # canonical form: the truncate element appears ONLY when
            # False (gpt-oss), so truncate-True configs keep the 6-tuple
            # shape existing presets/round-trips use
        ) + ((False,) if not truncate else ())
    raise ValueError(f"unsupported rope_scaling type {rope_type!r}")


def _to_np(t) -> np.ndarray:
    """Torch tensor / numpy / jax array → numpy (bf16 via float32)."""
    if isinstance(t, np.ndarray):
        return t
    if hasattr(t, "detach"):  # torch
        t = t.detach()
        if str(t.dtype) == "torch.bfloat16":
            t = t.float()
        return t.cpu().numpy()
    return np.asarray(t)


def convert_state_dict(
    sd: dict, config: LlamaConfig, model_type: str = "llama"
) -> dict:
    """Flat HF state dict (name → tensor) → our nested params pytree.

    Accepts torch tensors, numpy, or jax arrays as values; returns
    ``config.dtype`` **host (numpy) arrays** with scanned ``[L, ...]``
    layer stacks — staying on host lets the caller ``jax.device_put``
    the tree straight into sharded device buffers (a 70B must never
    materialize on one chip; ml_dtypes provides the numpy bfloat16).
    """
    c = config
    dt = c.dtype
    if model_type in ("deepseek_v2", "deepseek_v3"):
        return _convert_deepseek(sd, c)
    if model_type in ("lfm2", "lfm2_moe"):
        return _convert_lfm2(sd, c)
    if model_type == "phi3":
        sd = _split_phi3(dict(sd), c)
    if model_type in ("glm", "glm4"):
        sd = _split_glm(dict(sd), c, model_type)
    if model_type == "nemotron":
        sd = _stack_nemotron_norms(dict(sd), c)
    if model_type == "starcoder2":
        sd = dict(sd)
        for i in range(c.n_layers):  # c_fc/c_proj → the unified names
            P = f"model.layers.{i}.mlp."
            for suff in ("weight", "bias"):
                if P + f"c_fc.{suff}" in sd:
                    sd[P + f"up_proj.{suff}"] = sd.pop(P + f"c_fc.{suff}")
                if P + f"c_proj.{suff}" in sd:
                    sd[P + f"down_proj.{suff}"] = sd.pop(P + f"c_proj.{suff}")
        sd = _stack_nemotron_norms(sd, c)  # same stacked-norm layout

    def get(name):
        if name not in sd:
            raise KeyError(
                f"missing weight {name!r} (have e.g. {sorted(sd)[:5]})"
            )
        return _to_np(sd[name])

    def stack(fmt, transpose=False):
        mats = []
        for i in range(c.n_layers):
            m = get(fmt.format(i=i))
            mats.append(m.T if transpose else m)
        return np.asarray(np.stack(mats), dt)

    if model_type in ("gemma3", "llama4"):
        # multimodal checkpoint: keep the text tower, drop the vision
        # weights. Both layouts normalize to model.*:
        #   language_model.model.layers...   (<= 4.51)
        #   model.language_model.layers...   (>= 4.52)
        stripped = {}
        for k, v in sd.items():
            if "language_model." not in k:
                continue  # vision tower / projector
            k = k.replace("model.language_model.", "model.", 1)
            k = k.replace("language_model.", "", 1)
            stripped[k] = v
        sd = stripped or sd
    llama4 = model_type in ("llama4", "llama4_text")

    P = "model.layers.{i}."
    # families whose pre-MLP norm is named pre_feedforward_layernorm
    # (sandwich-norm layouts; _split_glm renames glm4 into this shape)
    gemma2 = model_type in ("gemma2", "gemma3", "gemma3_text", "glm4")
    layers = {
        "wq": stack(P + "self_attn.q_proj.weight", transpose=True),
        "wk": stack(P + "self_attn.k_proj.weight", transpose=True),
        "wv": stack(P + "self_attn.v_proj.weight", transpose=True),
        "wo": stack(P + "self_attn.o_proj.weight", transpose=True),
    }
    if c.pre_norm:
        layers["attn_norm"] = stack(P + "input_layernorm.weight")
        if c.parallel_block:
            pass  # Cohere: attn_norm IS the shared norm (single leaf)
        else:
            # Gemma2's post_attention_layernorm norms the attention
            # *output*; everywhere else it is the pre-MLP norm
            layers["mlp_norm"] = stack(
                P + ("pre_feedforward_layernorm.weight" if gemma2
                     else "post_attention_layernorm.weight")
            )
    if c.qkv_bias:
        layers["bq"] = stack(P + "self_attn.q_proj.bias")
        layers["bk"] = stack(P + "self_attn.k_proj.bias")
        layers["bv"] = stack(P + "self_attn.v_proj.bias")
    if c.proj_bias:  # StarCoder2 / gpt-oss: o (and dense-MLP) biases
        layers["bo"] = stack(P + "self_attn.o_proj.bias")
        if not c.n_experts:
            layers["b_up"] = stack(P + "mlp.up_proj.bias")
            layers["b_down"] = stack(P + "mlp.down_proj.bias")
    if c.attn_sinks:
        layers["sinks"] = np.stack([
            _to_np(get(f"model.layers.{i}.self_attn.sinks")).astype(np.float32)
            for i in range(c.n_layers)
        ])
    if c.qk_norm or c.qk_norm_flat:
        layers["q_norm"] = stack(P + "self_attn.q_norm.weight")
        layers["k_norm"] = stack(P + "self_attn.k_norm.weight")
    if c.post_norms:
        layers["attn_post_norm"] = stack(P + "post_attention_layernorm.weight")
        layers["mlp_post_norm"] = stack(P + "post_feedforward_layernorm.weight")
    if c.n_experts and llama4:
        # Llama4 ships the experts FUSED and PRE-STACKED:
        #   experts.gate_up_proj [E, H, 2F]  (gate then up, no transpose)
        #   experts.down_proj    [E, F, H]
        #   router.weight        [E, H]  (nn.Linear [out, in])
        # plus a dense shared expert with plain Linear layout.
        gus, downs, routers = [], [], []
        for i in range(c.n_layers):
            F = f"model.layers.{i}.feed_forward."
            gus.append(_to_np(get(F + "experts.gate_up_proj")))
            downs.append(_to_np(get(F + "experts.down_proj")))
            routers.append(_to_np(get(F + "router.weight")).T)
        gu = np.stack(gus)  # [L, E, H, 2F]
        layers["w_gate"] = np.asarray(gu[..., : c.intermediate_size], dt)
        layers["w_up"] = np.asarray(gu[..., c.intermediate_size :], dt)
        layers["w_down"] = np.asarray(np.stack(downs), dt)
        layers["w_router"] = np.asarray(np.stack(routers), dt)
        SE = "feed_forward.shared_expert."
        layers["w_shared_gate"] = stack(P + SE + "gate_proj.weight", transpose=True)
        layers["w_shared_up"] = stack(P + SE + "up_proj.weight", transpose=True)
        layers["w_shared_down"] = stack(P + SE + "down_proj.weight", transpose=True)
    elif c.n_experts and model_type == "gpt_oss":
        # gpt-oss ships experts FUSED, PRE-STACKED and INTERLEAVED:
        #   experts.gate_up_proj [E, H, 2F] with gate = [..., ::2],
        #   up = [..., 1::2] (HF GptOssExperts), biases [E, 2F] the
        #   same way; down_proj [E, F, H] + bias [E, H]; router is a
        #   true Linear [E, H] + [E].
        gus, gubs, downs, downbs, routers, rbs = [], [], [], [], [], []
        for i in range(c.n_layers):
            F = f"model.layers.{i}.mlp."
            gus.append(_to_np(get(F + "experts.gate_up_proj")))
            gubs.append(_to_np(get(F + "experts.gate_up_proj_bias")))
            downs.append(_to_np(get(F + "experts.down_proj")))
            downbs.append(_to_np(get(F + "experts.down_proj_bias")))
            routers.append(_to_np(get(F + "router.weight")).T)
            rbs.append(_to_np(get(F + "router.bias")))
        gu = np.stack(gus)  # [L, E, H, 2F]
        gub = np.stack(gubs)  # [L, E, 2F]
        layers["w_gate"] = np.asarray(gu[..., ::2], dt)
        layers["w_up"] = np.asarray(gu[..., 1::2], dt)
        layers["b_gate"] = np.asarray(gub[..., ::2], dt)
        layers["b_up_e"] = np.asarray(gub[..., 1::2], dt)
        layers["w_down"] = np.asarray(np.stack(downs), dt)
        layers["b_down_e"] = np.asarray(np.stack(downbs), dt)
        layers["w_router"] = np.asarray(np.stack(routers), dt)
        layers["b_router"] = np.stack(rbs).astype(np.float32)
    elif c.n_experts:
        router, expert_prefix, (g, u, d) = _MOE_NAMES.get(
            model_type, _MOE_NAMES["mixtral"]
        )
        names = (("w_gate", g), ("w_up", u), ("w_down", d))
        layers["w_router"] = stack(P + router, transpose=True)
        for ours, theirs in names:
            per_layer = []
            for i in range(c.n_layers):
                per_layer.append(
                    np.stack([
                        get(f"model.layers.{i}.{expert_prefix}.{e}.{theirs}.weight").T
                        for e in range(c.n_experts)
                    ])
                )
            layers[ours] = np.asarray(np.stack(per_layer), dt)
    else:
        if not c.mlp_gateless:
            layers["w_gate"] = stack(P + "mlp.gate_proj.weight", transpose=True)
        layers["w_up"] = stack(P + "mlp.up_proj.weight", transpose=True)
        layers["w_down"] = stack(P + "mlp.down_proj.weight", transpose=True)

    params = {
        "embed": np.asarray(get("model.embed_tokens.weight"), dt),
        "layers": layers,
        "final_norm": np.asarray(get("model.norm.weight"), dt),
    }
    if not c.tie_embeddings:
        params["lm_head"] = np.asarray(get("lm_head.weight").T, dt)
    return params


def _convert_deepseek(sd: dict, c: LlamaConfig) -> dict:
    """DeepSeek-V2/V3 state dict → params: MLA projections plus the
    dense-prelude/MoE layer split (``first_k_dense`` layers stack into
    ``dense_layers``, the rest into ``layers``)."""
    dt = c.dtype

    def get(name):
        if name not in sd:
            raise KeyError(
                f"missing weight {name!r} (have e.g. {sorted(sd)[:5]})"
            )
        return _to_np(sd[name])

    def stack(fmt, rows, transpose=False):
        mats = [get(fmt.format(i=i)) for i in rows]
        if transpose:
            mats = [m.T for m in mats]
        return np.asarray(np.stack(mats), dt)

    def attn_and_norms(rows):
        A = "model.layers.{i}.self_attn."
        d = {
            "attn_norm": stack("model.layers.{i}.input_layernorm.weight", rows),
            "mlp_norm": stack(
                "model.layers.{i}.post_attention_layernorm.weight", rows
            ),
            "wkv_a": stack(A + "kv_a_proj_with_mqa.weight", rows, transpose=True),
            "kv_a_norm": stack(A + "kv_a_layernorm.weight", rows),
            "wkv_b": stack(A + "kv_b_proj.weight", rows, transpose=True),
            "wo": stack(A + "o_proj.weight", rows, transpose=True),
        }
        if c.q_lora_rank:
            d["wq_a"] = stack(A + "q_a_proj.weight", rows, transpose=True)
            d["q_a_norm"] = stack(A + "q_a_layernorm.weight", rows)
            d["wq_b"] = stack(A + "q_b_proj.weight", rows, transpose=True)
        else:
            d["wq"] = stack(A + "q_proj.weight", rows, transpose=True)
        return d

    def dense_mlp(rows):
        return {
            "w_gate": stack("model.layers.{i}.mlp.gate_proj.weight", rows, transpose=True),
            "w_up": stack("model.layers.{i}.mlp.up_proj.weight", rows, transpose=True),
            "w_down": stack("model.layers.{i}.mlp.down_proj.weight", rows, transpose=True),
        }

    K = c.first_k_dense
    main_rows = list(range(K, c.n_layers))
    layers = attn_and_norms(main_rows)
    if c.n_experts:
        layers["w_router"] = stack(
            "model.layers.{i}.mlp.gate.weight", main_rows, transpose=True
        )
        if c.router_bias:
            layers["router_bias"] = np.asarray(
                np.stack([
                    get(f"model.layers.{i}.mlp.gate.e_score_correction_bias")
                    for i in main_rows
                ]),
                np.float32,  # selection bias stays f32 (HF buffer dtype)
            )
        for ours, theirs in (
            ("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj")
        ):
            layers[ours] = np.asarray(
                np.stack([
                    np.stack([
                        get(
                            f"model.layers.{i}.mlp.experts.{e}.{theirs}.weight"
                        ).T
                        for e in range(c.n_experts)
                    ])
                    for i in main_rows
                ]),
                dt,
            )
        if c.moe_shared_expert:
            S = "model.layers.{i}.mlp.shared_experts."
            layers["w_shared_gate"] = stack(S + "gate_proj.weight", main_rows, transpose=True)
            layers["w_shared_up"] = stack(S + "up_proj.weight", main_rows, transpose=True)
            layers["w_shared_down"] = stack(S + "down_proj.weight", main_rows, transpose=True)
    else:
        layers.update(dense_mlp(main_rows))

    params = {
        "embed": np.asarray(get("model.embed_tokens.weight"), dt),
        "layers": layers,
        "final_norm": np.asarray(get("model.norm.weight"), dt),
    }
    if K:
        dense_rows = list(range(K))
        params["dense_layers"] = {
            **attn_and_norms(dense_rows), **dense_mlp(dense_rows)
        }
    if not c.tie_embeddings:
        params["lm_head"] = np.asarray(get("lm_head.weight").T, dt)
    return params


def _convert_lfm2(sd: dict, c: LlamaConfig) -> dict:
    """LFM2 state dict → our pytree (HF ``Lfm2ForCausalLM`` names): a
    stack a kind of layer (``layers``: the grouped-query ones,
    ``conv_layers``: the gated short convolutions), ``operator_norm`` /
    ``ffn_norm`` the two pre-norms, the dense FFN ``w1`` / ``w3`` /
    ``w2`` = gate / up / down, ``embedding_norm`` the last norm. The
    expert block's names (``lfm2_moe``) are on no machine here: they
    wait for a checkpoint or that module."""
    if c.n_experts:
        raise NotImplementedError(
            "lfm2_moe checkpoints: the expert block's state-dict names "
            "are not known here (config keys only)"
        )
    dt = c.dtype

    def get(name):
        if name not in sd:
            raise KeyError(f"missing weight {name!r} (have e.g. {sorted(sd)[:5]})")
        return _to_np(sd[name])

    def stack(ids, suffix, transpose=False):
        mats = [get(f"model.layers.{i}.{suffix}") for i in ids]
        return np.asarray(np.stack([m.T if transpose else m for m in mats]), dt)

    def common(ids):
        return {
            "attn_norm": stack(ids, "operator_norm.weight"),
            "mlp_norm": stack(ids, "ffn_norm.weight"),
            "w_gate": stack(ids, "feed_forward.w1.weight", transpose=True),
            "w_up": stack(ids, "feed_forward.w3.weight", transpose=True),
            "w_down": stack(ids, "feed_forward.w2.weight", transpose=True),
        }

    full = [i for i, k in enumerate(c.layer_types) if k == "full"]
    conv = [i for i, k in enumerate(c.layer_types) if k == "conv"]
    A = "self_attn."
    params = {
        "embed": np.asarray(get("model.embed_tokens.weight"), dt),
        "final_norm": np.asarray(get("model.embedding_norm.weight"), dt),
        "layers": {
            **common(full),
            "wq": stack(full, A + "q_proj.weight", transpose=True),
            "wk": stack(full, A + "k_proj.weight", transpose=True),
            "wv": stack(full, A + "v_proj.weight", transpose=True),
            "wo": stack(full, A + "out_proj.weight", transpose=True),
            "q_norm": stack(full, A + "q_layernorm.weight"),
            "k_norm": stack(full, A + "k_layernorm.weight"),
        },
        "conv_layers": {
            **common(conv),
            "conv_win": stack(conv, "conv.in_proj.weight", transpose=True),
            # Conv1d [H, 1, K] → taps [K, H] (tap K-1 on the current row)
            "conv_w": np.asarray(np.stack([
                get(f"model.layers.{i}.conv.conv.weight")[:, 0, :].T for i in conv
            ]), dt),
            "wo": stack(conv, "conv.out_proj.weight", transpose=True),
        },
    }
    if not c.tie_embeddings:
        params["lm_head"] = np.asarray(get("lm_head.weight").T, dt)
    return params


def _stack_nemotron_norms(sd: dict, c: LlamaConfig) -> dict:
    """Nemotron LayerNorm1P carries weight AND bias; our tree stores
    them stacked [2, H] (scale-1 row then bias row — the checkpoint's
    weight already IS scale-1 since forward uses weight + 1)."""
    names = ["model.norm"]
    for i in range(c.n_layers):
        names += [
            f"model.layers.{i}.input_layernorm",
            f"model.layers.{i}.post_attention_layernorm",
        ]
    for n in names:
        w = _to_np(sd.pop(n + ".weight"))
        b = _to_np(sd.pop(n + ".bias"))
        sd[n + ".weight"] = np.stack([w, b])
    return sd


def _split_glm(sd: dict, c: LlamaConfig, model_type: str) -> dict:
    """GLM fuses gate/up into ``gate_up_proj`` ([2F, H] rows: gate then
    up) — split it; glm4's sandwich norms are renamed into the
    Gemma2-style names the generic path reads (post_self_attn →
    post_attention, post_attention → pre_feedforward, post_mlp →
    post_feedforward)."""
    F = c.intermediate_size
    for i in range(c.n_layers):
        P = f"model.layers.{i}."
        gu = _to_np(sd.pop(P + "mlp.gate_up_proj.weight"))
        sd[P + "mlp.gate_proj.weight"] = gu[:F]
        sd[P + "mlp.up_proj.weight"] = gu[F:]
        if model_type == "glm4":
            attn_post = sd.pop(P + "post_self_attn_layernorm.weight")
            pre_mlp = sd.pop(P + "post_attention_layernorm.weight")
            mlp_post = sd.pop(P + "post_mlp_layernorm.weight")
            sd[P + "post_attention_layernorm.weight"] = attn_post
            sd[P + "pre_feedforward_layernorm.weight"] = pre_mlp
            sd[P + "post_feedforward_layernorm.weight"] = mlp_post
    return sd


def _split_phi3(sd: dict, c: LlamaConfig) -> dict:
    """Phi-3 fuses q/k/v into ``qkv_proj`` and gate/up into
    ``gate_up_proj`` ([out, in] rows: q then k then v; gate then up) —
    split them into the standard per-projection names."""
    for i in range(c.n_layers):
        P = f"model.layers.{i}."
        qkv = _to_np(sd.pop(P + "self_attn.qkv_proj.weight"))
        q, k, v = np.split(qkv, [c.q_dim, c.q_dim + c.kv_dim], axis=0)
        sd[P + "self_attn.q_proj.weight"] = q
        sd[P + "self_attn.k_proj.weight"] = k
        sd[P + "self_attn.v_proj.weight"] = v
        gu = _to_np(sd.pop(P + "mlp.gate_up_proj.weight"))
        gate, up = np.split(gu, 2, axis=0)
        sd[P + "mlp.gate_proj.weight"] = gate
        sd[P + "mlp.up_proj.weight"] = up
    return sd


def _load_raw_state_dict(path: Path) -> dict:
    """Read all weight shards in a ``save_pretrained`` directory."""
    safes = sorted(path.glob("*.safetensors"))
    if safes:
        from safetensors import safe_open

        sd = {}
        for f in safes:
            # framework="pt": torch tensors carry bf16 losslessly;
            # _to_np upcasts on conversion
            with safe_open(f, framework="pt") as st:
                for name in st.keys():
                    sd[name] = st.get_tensor(name)
        return sd
    bins = sorted(path.glob("pytorch_model*.bin"))
    if bins:
        import torch

        sd = {}
        for f in bins:
            sd.update(torch.load(f, map_location="cpu", weights_only=True))
        return sd
    raise FileNotFoundError(f"no *.safetensors or pytorch_model*.bin in {path}")


def load_checkpoint(
    path: str, dtype: Any = jnp.bfloat16
) -> tuple[LlamaConfig, dict]:
    """Load an HF ``save_pretrained`` directory → (config, params)."""
    p = Path(path)
    hf = json.loads((p / "config.json").read_text())
    config = config_from_hf(hf, dtype=dtype)
    sd = _load_raw_state_dict(p)
    params = convert_state_dict(sd, config, hf.get("model_type", "llama"))
    return config, params


def config_to_hf(config: LlamaConfig) -> dict:
    """:class:`LlamaConfig` → HF ``config.json`` dict (inverse of
    :func:`config_from_hf` for the families we can express)."""
    c = config
    if c.attn_sinks or c.moe_bias or c.router_topk_softmax:
        # the generic MoE branch would tag this "mixtral" and silently
        # drop sinks/expert biases/router semantics — refuse rather
        # than mis-export (module policy); re-serve gpt-oss fine-tunes
        # through this framework's engine instead
        raise ValueError(
            "gpt-oss configs (attention sinks / biased experts / "
            "topk-softmax router) cannot be exported as an HF "
            "checkpoint yet"
        )
    hf = {
        "hidden_act": (
            "gelu_pytorch_tanh" if c.hidden_act == "gelu_tanh" else "silu"
        ),
        "vocab_size": c.vocab_size,
        "hidden_size": c.hidden_size,
        "num_hidden_layers": c.n_layers,
        "num_attention_heads": c.n_heads,
        "num_key_value_heads": c.n_kv_heads,
        "head_dim": c.head_dim,
        "intermediate_size": c.intermediate_size,
        "rope_theta": c.rope_theta,
        "rms_norm_eps": c.norm_eps,
        "max_position_embeddings": c.max_seq_len,
        "tie_word_embeddings": c.tie_embeddings,
        "torch_dtype": "bfloat16",
    }
    if c.rope_scaling is not None and c.rope_scaling[0] == "linear":
        hf["rope_scaling"] = {
            "rope_type": "linear", "factor": float(c.rope_scaling[1])
        }
    elif c.rope_scaling is not None and c.rope_scaling[0] == "yarn":
        _, factor, beta_fast, beta_slow, orig, att = c.rope_scaling[:6]
        hf["rope_scaling"] = {
            "rope_type": "yarn",
            "factor": factor,
            "beta_fast": beta_fast,
            "beta_slow": beta_slow,
            "original_max_position_embeddings": int(orig),
            "attention_factor": att,  # resolved; HF reads it directly
        }
        if len(c.rope_scaling) > 6:  # gpt-oss: truncate=false round trip
            hf["rope_scaling"]["truncate"] = bool(c.rope_scaling[6])
    elif c.rope_scaling is not None:
        rs = c.rope_scaling
        factor, low_f, high_f, orig = rs[1:] if rs[0] == "llama3" else rs
        hf["rope_scaling"] = {
            "rope_type": "llama3",
            "factor": factor,
            "low_freq_factor": low_f,
            "high_freq_factor": high_f,
            "original_max_position_embeddings": int(orig),
        }
    if c.mla:
        v3 = c.router_score == "sigmoid"
        hf.update(
            model_type="deepseek_v3" if v3 else "deepseek_v2",
            head_dim=c.qk_rope_head_dim,  # HF rope dim for deepseek
            q_lora_rank=c.q_lora_rank or None,
            kv_lora_rank=c.kv_lora_rank,
            qk_nope_head_dim=c.qk_nope_head_dim,
            qk_rope_head_dim=c.qk_rope_head_dim,
            v_head_dim=c.v_head_dim,
        )
        if (
            (v3 or _v2_mscale_fix())
            and c.attn_scale is not None
            and "rope_scaling" in hf
        ):
            # invert the mscale^2 softmax-scale correction back into
            # mscale_all_dim so HF reapplies it (and our loader
            # re-derives attn_scale on the round trip; V2 only when the
            # fix flag is on — mirrors the load-side gate)
            factor = hf["rope_scaling"]["factor"]
            ms = math.sqrt(c.attn_scale * c.qk_head_dim**0.5)
            hf["rope_scaling"]["mscale_all_dim"] = (
                (ms - 1.0) / (0.1 * math.log(factor))
            )
        if c.n_experts:
            shared = (
                c.moe_shared_intermediate // c.intermediate_size
                if c.moe_shared_expert else None
            )
            hf.update(
                n_routed_experts=c.n_experts,
                num_experts_per_tok=c.experts_per_token,
                moe_intermediate_size=c.intermediate_size,
                intermediate_size=c.dense_intermediate or c.intermediate_size,
                first_k_dense_replace=c.first_k_dense,
                moe_layer_freq=1,
                n_shared_experts=shared,
                norm_topk_prob=c.router_renorm,
                routed_scaling_factor=c.routed_scale,
            )
            if v3:
                hf.update(
                    n_group=c.router_groups[0] if c.router_groups else 1,
                    topk_group=c.router_groups[1] if c.router_groups else 1,
                )
            else:
                hf.update(
                    topk_method=(
                        "group_limited_greedy" if c.router_groups else "greedy"
                    ),
                    n_group=c.router_groups[0] if c.router_groups else None,
                    topk_group=c.router_groups[1] if c.router_groups else None,
                )
        else:
            # all-dense MLA: no layer reaches the MoE branch
            hf.update(first_k_dense_replace=c.n_layers, n_routed_experts=None)
        return hf
    if not c.pre_norm:
        hf.update(model_type="olmo2")
        return hf
    if c.embed_multiplier or c.residual_multiplier:
        hf.update(
            model_type="granite",
            embedding_multiplier=c.embed_multiplier or 1.0,
            residual_multiplier=c.residual_multiplier or 1.0,
            # None means the default 1/sqrt(head_dim) — emit the real
            # value so a save/load roundtrip keeps the softmax scale
            attention_multiplier=(
                c.attn_scale if c.attn_scale is not None
                else c.qk_head_dim**-0.5
            ),
            logits_scaling=(1.0 / c.logit_scale) if c.logit_scale else 1.0,
        )
        return hf
    if c.parallel_block:
        if c.sliding_window:
            if c.qk_norm or c.nope_pattern != c.sliding_pattern:
                raise ValueError(
                    "cohere2 export requires nope_pattern == "
                    "sliding_pattern and no qk_norm (the HF config "
                    "cannot express other layouts)"
                )
            hf.update(
                model_type="cohere2",
                layer_norm_eps=c.norm_eps,
                logit_scale=c.logit_scale,
                sliding_window=c.sliding_window,
                sliding_window_pattern=c.sliding_pattern,
                layer_types=[
                    "sliding_attention" if w else "full_attention"
                    for w in _layer_windows(c)
                ],
            )
        else:
            hf.update(
                model_type="cohere",
                layer_norm_eps=c.norm_eps,
                logit_scale=c.logit_scale,
                use_qk_norm=c.qk_norm,
            )
        return hf
    if c.norm_type == "layernorm_bias":
        hf.update(
            model_type="starcoder2",
            norm_epsilon=c.norm_eps,
            use_bias=c.proj_bias,
            sliding_window=c.sliding_window or None,
        )
        return hf
    if c.norm_type == "layernorm1p":
        hf.update(
            model_type="nemotron",
            norm_eps=c.norm_eps,
            partial_rotary_factor=c.partial_rotary,
        )
        hf["hidden_act"] = "relu2"
        return hf
    if c.partial_rotary != 1.0:
        hf.update(
            model_type="glm4" if c.post_norms else "glm",
            attention_bias=c.qkv_bias,
            partial_rotary_factor=c.partial_rotary,
        )
        return hf
    if c.rope_interleaved:
        from dstack_tpu.models.llama import layer_nope as _layer_nope

        hf.update(
            model_type="llama4_text",
            no_rope_layers=[0 if n else 1 for n in _layer_nope(c)],
            attention_chunk_size=c.attention_chunk_size or None,
            use_qk_norm=c.qk_l2_norm,
            attn_temperature_tuning=bool(c.attn_temp_scale),
            attn_scale=c.attn_temp_scale or 0.1,
            floor_scale=c.attn_temp_floor,
            num_local_experts=c.n_experts,
            num_experts_per_tok=c.experts_per_token,
            interleave_moe_layer_step=1,
            intermediate_size_mlp=c.intermediate_size,
        )
    elif c.n_experts and c.qk_norm:
        hf.update(
            model_type="qwen3_moe",
            num_experts=c.n_experts,
            num_experts_per_tok=c.experts_per_token,
            moe_intermediate_size=c.intermediate_size,
            norm_topk_prob=c.router_renorm,
            attention_bias=c.qkv_bias,
        )
    elif c.n_experts:
        hf.update(
            model_type="mixtral",
            num_local_experts=c.n_experts,
            num_experts_per_tok=c.experts_per_token,
        )
    elif c.rope_local_theta:
        hf.update(
            model_type="gemma3_text",
            sliding_window=c.sliding_window or None,
            sliding_window_pattern=c.sliding_pattern or None,
            layer_types=[
                "sliding_attention" if w else "full_attention"
                for w in _layer_windows(c)
            ],
            rope_local_base_freq=c.rope_local_theta,
            query_pre_attn_scalar=(
                round(c.attn_scale**-2) if c.attn_scale else c.head_dim
            ),
        )
    elif c.post_norms:
        hf.update(
            model_type="gemma2",
            sliding_window=c.sliding_window or None,
            attn_logit_softcapping=c.attn_softcap or None,
            final_logit_softcapping=c.logit_softcap or None,
            query_pre_attn_scalar=(
                round(c.attn_scale**-2) if c.attn_scale else c.head_dim
            ),
        )
    elif c.norm_offset:
        hf.update(model_type="gemma")
    elif c.qk_norm:
        hf.update(model_type="qwen3", attention_bias=c.qkv_bias)
    elif c.qkv_bias:
        hf.update(model_type="qwen2")
        if c.sliding_window:
            hf.update(
                use_sliding_window=True,
                sliding_window=c.sliding_window,
                max_window_layers=0,
            )
    elif c.sliding_window:
        hf.update(model_type="mistral", sliding_window=c.sliding_window)
    else:
        hf.update(model_type="llama")
    return hf


def export_state_dict(params: dict, config: LlamaConfig) -> dict:
    """Our params pytree → flat HF state dict (numpy values) — the
    inverse of :func:`convert_state_dict`, so fine-tuned weights serve
    anywhere HF checkpoints do (vLLM, TGI, transformers)."""
    from dstack_tpu.models.quant import is_quantized

    if is_quantized(params):
        raise ValueError("export requires full-precision params, not int8")
    c = config
    mt = config_to_hf(c)["model_type"]
    if mt in ("deepseek_v2", "deepseek_v3"):
        return _export_deepseek(params, c)
    gemma2 = mt in ("gemma2", "gemma3_text", "glm4")

    def np32(x):
        # keep the source dtype (bf16 stays bf16): upcasting every
        # tensor to f32 here would stage a 70B at ~2x its size on host
        return np.asarray(jax.device_get(x))

    sd: dict = {"model.embed_tokens.weight": np32(params["embed"])}
    L = params["layers"]
    for i in range(c.n_layers):
        P = f"model.layers.{i}."
        sd[P + "self_attn.q_proj.weight"] = np32(L["wq"][i]).T
        sd[P + "self_attn.k_proj.weight"] = np32(L["wk"][i]).T
        sd[P + "self_attn.v_proj.weight"] = np32(L["wv"][i]).T
        sd[P + "self_attn.o_proj.weight"] = np32(L["wo"][i]).T
        if c.pre_norm:
            sd[P + "input_layernorm.weight"] = np32(L["attn_norm"][i])
            if not c.parallel_block:  # Cohere's single norm is aliased
                mlp_norm_name = (
                    "pre_feedforward_layernorm.weight" if gemma2
                    else "post_attention_layernorm.weight"
                )
                sd[P + mlp_norm_name] = np32(L["mlp_norm"][i])
        if c.qkv_bias:
            sd[P + "self_attn.q_proj.bias"] = np32(L["bq"][i])
            sd[P + "self_attn.k_proj.bias"] = np32(L["bk"][i])
            sd[P + "self_attn.v_proj.bias"] = np32(L["bv"][i])
        if c.proj_bias:
            sd[P + "self_attn.o_proj.bias"] = np32(L["bo"][i])
            sd[P + "mlp.up_proj.bias"] = np32(L["b_up"][i])
            sd[P + "mlp.down_proj.bias"] = np32(L["b_down"][i])
        if c.qk_norm or c.qk_norm_flat:
            sd[P + "self_attn.q_norm.weight"] = np32(L["q_norm"][i])
            sd[P + "self_attn.k_norm.weight"] = np32(L["k_norm"][i])
        if c.post_norms:
            sd[P + "post_attention_layernorm.weight"] = np32(L["attn_post_norm"][i])
            sd[P + "post_feedforward_layernorm.weight"] = np32(L["mlp_post_norm"][i])
        if c.n_experts and mt == "llama4_text":
            # fused pre-stacked layout (see convert_state_dict)
            F = P + "feed_forward."
            sd[F + "router.weight"] = np32(L["w_router"][i]).T
            sd[F + "experts.gate_up_proj"] = np.concatenate(
                [np32(L["w_gate"][i]), np32(L["w_up"][i])], axis=-1
            )
            sd[F + "experts.down_proj"] = np32(L["w_down"][i])
            SE = F + "shared_expert."
            sd[SE + "gate_proj.weight"] = np32(L["w_shared_gate"][i]).T
            sd[SE + "up_proj.weight"] = np32(L["w_shared_up"][i]).T
            sd[SE + "down_proj.weight"] = np32(L["w_shared_down"][i]).T
        elif c.n_experts:
            router, eprefix, (g, u, d) = _MOE_NAMES.get(
                mt, _MOE_NAMES["mixtral"]
            )
            sd[P + router] = np32(L["w_router"][i]).T
            for e in range(c.n_experts):
                E = P + f"{eprefix}.{e}."
                sd[E + f"{g}.weight"] = np32(L["w_gate"][i][e]).T
                sd[E + f"{u}.weight"] = np32(L["w_up"][i][e]).T
                sd[E + f"{d}.weight"] = np32(L["w_down"][i][e]).T
        else:
            if not c.mlp_gateless:
                sd[P + "mlp.gate_proj.weight"] = np32(L["w_gate"][i]).T
            sd[P + "mlp.up_proj.weight"] = np32(L["w_up"][i]).T
            sd[P + "mlp.down_proj.weight"] = np32(L["w_down"][i]).T
    sd["model.norm.weight"] = np32(params["final_norm"])
    if c.norm_type in ("layernorm1p", "layernorm_bias"):
        # split the stacked (scale, bias) rows back into HF names
        stacked = [n for n in sd if n.endswith("layernorm.weight")]
        for n in stacked + ["model.norm.weight"]:
            wb = sd.pop(n)
            sd[n] = wb[0]
            sd[n[: -len(".weight")] + ".bias"] = wb[1]
    if c.norm_type == "layernorm_bias":
        # back to StarCoder2's c_fc/c_proj MLP names
        for i in range(c.n_layers):
            P = f"model.layers.{i}.mlp."
            for suff in ("weight", "bias"):
                if P + f"up_proj.{suff}" in sd:
                    sd[P + f"c_fc.{suff}"] = sd.pop(P + f"up_proj.{suff}")
                if P + f"down_proj.{suff}" in sd:
                    sd[P + f"c_proj.{suff}"] = sd.pop(P + f"down_proj.{suff}")
    if not c.tie_embeddings:
        sd["lm_head.weight"] = np32(params["lm_head"]).T
    if mt in ("glm", "glm4"):
        # inverse of _split_glm: re-fuse gate/up; restore glm4 norm names
        for i in range(c.n_layers):
            P = f"model.layers.{i}."
            sd[P + "mlp.gate_up_proj.weight"] = np.concatenate(
                [sd.pop(P + "mlp.gate_proj.weight"),
                 sd.pop(P + "mlp.up_proj.weight")],
                axis=0,
            )
            if mt == "glm4":
                attn_post = sd.pop(P + "post_attention_layernorm.weight")
                pre_mlp = sd.pop(P + "pre_feedforward_layernorm.weight")
                mlp_post = sd.pop(P + "post_feedforward_layernorm.weight")
                sd[P + "post_self_attn_layernorm.weight"] = attn_post
                sd[P + "post_attention_layernorm.weight"] = pre_mlp
                sd[P + "post_mlp_layernorm.weight"] = mlp_post
    return sd


def _export_deepseek(params: dict, c: LlamaConfig) -> dict:
    """Inverse of :func:`_convert_deepseek` (flat HF names, numpy)."""

    def np_(x):
        return np.asarray(jax.device_get(x))

    sd: dict = {"model.embed_tokens.weight": np_(params["embed"])}

    def put_layer(sd_row, i, moe):
        P = f"model.layers.{i}."
        A = P + "self_attn."
        sd[P + "input_layernorm.weight"] = np_(sd_row["attn_norm"])
        sd[P + "post_attention_layernorm.weight"] = np_(sd_row["mlp_norm"])
        sd[A + "kv_a_proj_with_mqa.weight"] = np_(sd_row["wkv_a"]).T
        sd[A + "kv_a_layernorm.weight"] = np_(sd_row["kv_a_norm"])
        sd[A + "kv_b_proj.weight"] = np_(sd_row["wkv_b"]).T
        sd[A + "o_proj.weight"] = np_(sd_row["wo"]).T
        if c.q_lora_rank:
            sd[A + "q_a_proj.weight"] = np_(sd_row["wq_a"]).T
            sd[A + "q_a_layernorm.weight"] = np_(sd_row["q_a_norm"])
            sd[A + "q_b_proj.weight"] = np_(sd_row["wq_b"]).T
        else:
            sd[A + "q_proj.weight"] = np_(sd_row["wq"]).T
        if moe:
            sd[P + "mlp.gate.weight"] = np_(sd_row["w_router"]).T
            if c.router_bias:
                sd[P + "mlp.gate.e_score_correction_bias"] = np_(
                    sd_row["router_bias"]
                )
            for e in range(c.n_experts):
                E = P + f"mlp.experts.{e}."
                sd[E + "gate_proj.weight"] = np_(sd_row["w_gate"][e]).T
                sd[E + "up_proj.weight"] = np_(sd_row["w_up"][e]).T
                sd[E + "down_proj.weight"] = np_(sd_row["w_down"][e]).T
            if c.moe_shared_expert:
                S = P + "mlp.shared_experts."
                sd[S + "gate_proj.weight"] = np_(sd_row["w_shared_gate"]).T
                sd[S + "up_proj.weight"] = np_(sd_row["w_shared_up"]).T
                sd[S + "down_proj.weight"] = np_(sd_row["w_shared_down"]).T
        else:
            sd[P + "mlp.gate_proj.weight"] = np_(sd_row["w_gate"]).T
            sd[P + "mlp.up_proj.weight"] = np_(sd_row["w_up"]).T
            sd[P + "mlp.down_proj.weight"] = np_(sd_row["w_down"]).T

    K = c.first_k_dense
    for j in range(K):
        put_layer(
            jax.tree.map(lambda a: a[j], params["dense_layers"]), j, False
        )
    for j in range(c.n_layers - K):
        put_layer(
            jax.tree.map(lambda a: a[j], params["layers"]), K + j,
            bool(c.n_experts),
        )
    sd["model.norm.weight"] = np_(params["final_norm"])
    if not c.tie_embeddings:
        sd["lm_head.weight"] = np_(params["lm_head"]).T
    return sd


def save_checkpoint(config: LlamaConfig, params: dict, path: str) -> None:
    """Write an HF ``save_pretrained``-compatible directory
    (config.json + model.safetensors, bf16).

    The tensors go through torch: safetensors' numpy API mangles
    ml_dtypes bfloat16 arrays (verified: values corrupt on round trip),
    while the torch API stores bf16 natively.
    """
    import ml_dtypes
    import torch
    from safetensors.torch import save_file

    def to_torch_bf16(v: np.ndarray):
        v = np.ascontiguousarray(v)
        if v.dtype == ml_dtypes.bfloat16:
            # bit-exact reinterpretation, no f32 staging
            return torch.from_numpy(v.view(np.uint16)).view(torch.bfloat16)
        return torch.from_numpy(np.asarray(v, np.float32)).to(torch.bfloat16)

    p = Path(path)
    p.mkdir(parents=True, exist_ok=True)
    (p / "config.json").write_text(json.dumps(config_to_hf(config), indent=2))
    sd = export_state_dict(params, config)
    save_file(
        {k: to_torch_bf16(v) for k, v in sd.items()},
        str(p / "model.safetensors"),
    )
