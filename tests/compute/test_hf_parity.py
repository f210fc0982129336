"""Logit-parity against HuggingFace transformers (CPU, tiny models).

The strongest correctness check the model families can get without
downloading weights: build a tiny randomly-initialized HF model per
family, save_pretrained → models/convert_hf.load_checkpoint → compare
our f32 forward logits to the torch forward, position by position.
Covers weight-layout mapping, RoPE convention, GQA, biases, norms
(offset/sandwich), activations, sliding windows, softcaps, and MoE
routing in one assertion per family.
"""

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax
import jax.numpy as jnp

from dstack_tpu.models import llama
from dstack_tpu.models.convert_hf import load_checkpoint
from tests.shared import init_params

B, T = 2, 16


def _save_tiny(tmp_path, config_cls, model_cls, **kw):
    torch.manual_seed(0)
    cfg = config_cls(**{
        "vocab_size": 128,
        "hidden_size": 64,
        "intermediate_size": 96,
        "num_hidden_layers": 4,
        "num_attention_heads": 4,
        "num_key_value_heads": 2,
        "max_position_embeddings": 64,
        **kw,
    })
    model = model_cls(cfg)
    model.eval()
    model.save_pretrained(tmp_path)
    return model


def _assert_parity(tmp_path, hf_model, atol=2e-4, **fwd_kw):
    config, params = load_checkpoint(str(tmp_path), dtype=jnp.float32)
    params = jax.device_put(params)  # converter returns host arrays
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, config.vocab_size, (B, T))
    with torch.no_grad():
        ref = hf_model(torch.tensor(tokens)).logits.numpy()
    config = llama.dataclasses.replace(config, remat=False)
    ours = llama.forward(params, jnp.asarray(tokens), config, **fwd_kw)
    np.testing.assert_allclose(np.asarray(ours), ref, rtol=1e-3, atol=atol)
    return config


class TestHFParity:
    def test_llama(self, tmp_path):
        m = _save_tiny(
            tmp_path, transformers.LlamaConfig, transformers.LlamaForCausalLM,
            rope_theta=10000.0, tie_word_embeddings=False,
        )
        cfg = _assert_parity(tmp_path, m)
        assert not cfg.qkv_bias and cfg.sliding_window == 0

    def test_llama_tied_embeddings(self, tmp_path):
        m = _save_tiny(
            tmp_path, transformers.LlamaConfig, transformers.LlamaForCausalLM,
            tie_word_embeddings=True,
        )
        cfg = _assert_parity(tmp_path, m)
        assert cfg.tie_embeddings

    def test_llama31_rope_scaling(self, tmp_path):
        """rope_type llama3 (Llama-3.1/3.2 checkpoints) rescales rope
        frequencies — must match HF, and differ from unscaled rope."""
        m = _save_tiny(
            tmp_path, transformers.LlamaConfig, transformers.LlamaForCausalLM,
            rope_theta=10000.0,
            rope_scaling={
                "rope_type": "llama3", "factor": 8.0,
                "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                "original_max_position_embeddings": 8,
            },
        )
        cfg = _assert_parity(tmp_path, m)
        assert cfg.rope_scaling == (8.0, 1.0, 4.0, 8.0)
        config, params = load_checkpoint(str(tmp_path), dtype=jnp.float32)
        params = jax.device_put(params)
        config = llama.dataclasses.replace(config, remat=False)
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(0, config.vocab_size, (B, T)))
        scaled = llama.forward(params, tokens, config)
        plain = llama.forward(
            params, tokens, llama.dataclasses.replace(config, rope_scaling=None)
        )
        assert not np.allclose(np.asarray(scaled), np.asarray(plain))

    def test_unsupported_rope_scaling_rejected(self, tmp_path):
        import json
        from dstack_tpu.models.convert_hf import config_from_hf

        hf = json.loads((_save_tiny(
            tmp_path, transformers.LlamaConfig, transformers.LlamaForCausalLM,
        ).config.to_json_string()))
        hf["rope_scaling"] = {"rope_type": "longrope", "factor": 4.0}
        with pytest.raises(ValueError, match="rope_scaling"):
            config_from_hf(hf)

    def test_qwen2(self, tmp_path):
        m = _save_tiny(
            tmp_path, transformers.Qwen2Config, transformers.Qwen2ForCausalLM,
        )
        cfg = _assert_parity(tmp_path, m)
        assert cfg.qkv_bias

    def test_qwen3_qk_norm(self, tmp_path):
        m = _save_tiny(
            tmp_path, transformers.Qwen3Config, transformers.Qwen3ForCausalLM,
            head_dim=16,
        )
        cfg = _assert_parity(tmp_path, m)
        assert cfg.qk_norm and not cfg.qkv_bias

    def test_mistral_sliding_window(self, tmp_path):
        # window < T so the mask actually bites
        m = _save_tiny(
            tmp_path, transformers.MistralConfig, transformers.MistralForCausalLM,
            sliding_window=8,
        )
        cfg = _assert_parity(tmp_path, m)
        assert cfg.sliding_window == 8
        # and the windowed logits differ from a full-attention run
        config, params = load_checkpoint(str(tmp_path), dtype=jnp.float32)
        params = jax.device_put(params)
        config = llama.dataclasses.replace(
            config, remat=False, sliding_window=0
        )
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(0, config.vocab_size, (B, T)))
        full = llama.forward(params, tokens, config)
        windowed = llama.forward(
            params, tokens, llama.dataclasses.replace(config, sliding_window=8)
        )
        assert not np.allclose(np.asarray(full), np.asarray(windowed))

    def test_gemma(self, tmp_path):
        m = _save_tiny(
            tmp_path, transformers.GemmaConfig, transformers.GemmaForCausalLM,
            head_dim=16,
        )
        cfg = _assert_parity(tmp_path, m, atol=5e-4)
        assert cfg.norm_offset and cfg.embed_scale
        assert cfg.hidden_act == "gelu_tanh"

    def test_gemma2(self, tmp_path):
        m = _save_tiny(
            tmp_path, transformers.Gemma2Config, transformers.Gemma2ForCausalLM,
            head_dim=16,
            sliding_window=8,
            attn_logit_softcapping=50.0,
            final_logit_softcapping=30.0,
            query_pre_attn_scalar=16,
        )
        cfg = _assert_parity(tmp_path, m, atol=5e-4)
        assert cfg.post_norms and cfg.attn_softcap == 50.0
        assert cfg.sliding_pattern == 2
        # layer windows alternate sliding/global, HF convention
        assert llama.layer_windows(cfg) == [8, 0, 8, 0]

    def test_gemma3(self, tmp_path):
        """Dual rope theta (local 10k on sliding layers, global 1M),
        qk-norm with the Gemma zero-centered weights, alternating
        windows, sandwich norms — the full Gemma3 delta set."""
        m = _save_tiny(
            tmp_path, transformers.Gemma3TextConfig,
            transformers.Gemma3ForCausalLM,
            head_dim=16,
            sliding_window=8,
            layer_types=[
                "sliding_attention", "full_attention",
                "sliding_attention", "full_attention",
            ],
            rope_theta=1000000.0,
            rope_local_base_freq=10000.0,
            query_pre_attn_scalar=16,
        )
        cfg = _assert_parity(tmp_path, m, atol=5e-4)
        assert cfg.qk_norm and cfg.norm_offset and cfg.post_norms
        assert cfg.rope_local_theta == 10000.0
        assert cfg.sliding_pattern == 2 and cfg.sliding_window == 8
        assert llama.layer_windows(cfg) == [8, 0, 8, 0]
        # the dual rope actually matters: single-theta logits differ
        config, params = load_checkpoint(str(tmp_path), dtype=jnp.float32)
        params = jax.device_put(params)
        config = llama.dataclasses.replace(config, remat=False)
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(0, config.vocab_size, (B, T)))
        dual = llama.forward(params, tokens, config)
        single = llama.forward(
            params, tokens,
            llama.dataclasses.replace(config, rope_local_theta=0.0),
        )
        assert not np.allclose(np.asarray(dual), np.asarray(single))

    def test_gemma3_uneven_pattern(self, tmp_path):
        """Layer count not divisible by the sliding pattern (the real
        gemma-3 shapes: 26 layers, pattern 6) — the scan covers the
        full groups and the tail layers unroll after it."""
        m = _save_tiny(
            tmp_path, transformers.Gemma3TextConfig,
            transformers.Gemma3ForCausalLM,
            head_dim=16,
            sliding_window=8,
            num_hidden_layers=5,
            layer_types=[
                "sliding_attention", "sliding_attention", "full_attention",
                "sliding_attention", "sliding_attention",
            ],
            rope_theta=1000000.0,
            rope_local_base_freq=10000.0,
            query_pre_attn_scalar=16,
        )
        cfg = _assert_parity(tmp_path, m, atol=5e-4)
        assert cfg.sliding_pattern == 3 and cfg.n_layers == 5
        assert llama.layer_windows(cfg) == [8, 8, 0, 8, 8]

    def test_gemma3_linear_rope_scaling(self, tmp_path):
        """Global layers apply linear position interpolation; local
        layers stay unscaled (gemma-3-4b+ configs)."""
        m = _save_tiny(
            tmp_path, transformers.Gemma3TextConfig,
            transformers.Gemma3ForCausalLM,
            head_dim=16,
            sliding_window=8,
            layer_types=[
                "sliding_attention", "full_attention",
                "sliding_attention", "full_attention",
            ],
            rope_theta=1000000.0,
            rope_local_base_freq=10000.0,
            rope_scaling={"rope_type": "linear", "factor": 8.0},
            query_pre_attn_scalar=16,
        )
        cfg = _assert_parity(tmp_path, m, atol=5e-4)
        assert cfg.rope_scaling == ("linear", 8.0)

    def test_gemma3_multimodal_prefix_layouts(self, tmp_path):
        """Both multimodal key layouts (legacy language_model.model.*,
        newer model.language_model.*) normalize to the text layout;
        vision-tower keys are dropped."""
        import numpy as np
        from dstack_tpu.models.convert_hf import (
            _load_raw_state_dict,
            config_from_hf,
            convert_state_dict,
        )

        _save_tiny(
            tmp_path, transformers.Gemma3TextConfig,
            transformers.Gemma3ForCausalLM,
            head_dim=16, sliding_window=8,
            layer_types=["sliding_attention", "full_attention"] * 2,
            rope_theta=1000000.0, rope_local_base_freq=10000.0,
            query_pre_attn_scalar=16,
        )
        import json as _json
        hf = _json.loads((tmp_path / "config.json").read_text())
        config = config_from_hf(hf, dtype=jnp.float32)
        sd = _load_raw_state_dict(tmp_path)
        direct = convert_state_dict(dict(sd), config, "gemma3_text")
        legacy = {f"language_model.{k}": v for k, v in sd.items()}
        legacy["vision_tower.blocks.0.w"] = np.zeros((2, 2), np.float32)
        newer = {
            k.replace("model.", "model.language_model.", 1): v
            for k, v in sd.items()
        }
        newer["model.vision_tower.blocks.0.w"] = np.zeros((2, 2), np.float32)
        for variant in (legacy, newer):
            got = convert_state_dict(variant, config, "gemma3")
            np.testing.assert_array_equal(
                np.asarray(got["embed"]), np.asarray(direct["embed"])
            )
            np.testing.assert_array_equal(
                np.asarray(got["layers"]["wq"]), np.asarray(direct["layers"]["wq"])
            )

    def test_gemma3_all_global_layout_zeroes_window(self):
        """sliding_window set but every layer full_attention: the
        window must be dropped, not silently applied uniformly."""
        from dstack_tpu.models.convert_hf import config_from_hf

        cfg = config_from_hf({
            "model_type": "gemma3_text", "vocab_size": 128,
            "hidden_size": 64, "intermediate_size": 96,
            "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 16,
            "sliding_window": 512,
            "layer_types": ["full_attention", "full_attention"],
        })
        assert cfg.sliding_window == 0 and cfg.sliding_pattern == 0
        assert llama.layer_windows(cfg) == [0, 0]

    def test_phi3_fused_projections(self, tmp_path):
        m = _save_tiny(
            tmp_path, transformers.Phi3Config, transformers.Phi3ForCausalLM,
            pad_token_id=0,  # default 32000 exceeds the tiny vocab
        )
        cfg = _assert_parity(tmp_path, m)
        assert not cfg.qkv_bias and cfg.hidden_act == "silu"

    def test_mixtral(self, tmp_path):
        m = _save_tiny(
            tmp_path, transformers.MixtralConfig, transformers.MixtralForCausalLM,
            num_local_experts=4, num_experts_per_tok=2,
        )
        config, params = load_checkpoint(str(tmp_path), dtype=jnp.float32)
        params = jax.device_put(params)
        # no-drop capacity so the static dispatch is exact vs HF's
        # dynamic gather
        config = llama.dataclasses.replace(
            config, remat=False, capacity_factor=float(config.n_experts)
        )
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, config.vocab_size, (B, T))
        with torch.no_grad():
            ref = m(torch.tensor(tokens)).logits.numpy()
        ours = llama.forward(params, jnp.asarray(tokens), config)
        np.testing.assert_allclose(np.asarray(ours), ref, rtol=1e-3, atol=5e-4)


class TestLlama4:
    """Llama4 text tower: interleaved-pair rope, periodic NoPE layers,
    chunked attention, post-rope L2 qk norm, NoPE query temperature
    tuning, and the sigmoid-input-scaled MoE with a shared expert."""

    def _tiny(self, tmp_path, **kw):
        return _save_tiny(
            tmp_path, transformers.Llama4TextConfig,
            transformers.Llama4ForCausalLM,
            head_dim=16,
            num_local_experts=4,
            num_experts_per_tok=1,
            interleave_moe_layer_step=1,
            no_rope_layers=[1, 1, 1, 0],  # layer 3 NoPE
            attention_chunk_size=8,
            attn_temperature_tuning=True,
            attn_scale=0.1,
            floor_scale=4.0,
            use_qk_norm=True,
            rope_theta=500000.0,
            **kw,
        )

    def test_llama4_logit_parity(self, tmp_path):
        m = self._tiny(tmp_path)
        config, params = load_checkpoint(str(tmp_path), dtype=jnp.float32)
        assert config.rope_interleaved and config.qk_l2_norm
        assert config.nope_pattern == 4 and config.attention_chunk_size == 8
        assert config.router_sigmoid_input and config.moe_shared_expert
        assert llama.layer_nope(config) == [False, False, False, True]
        params = jax.device_put(params)
        # no-drop capacity: static dispatch exact vs HF dense compute
        config = llama.dataclasses.replace(
            config, remat=False, capacity_factor=float(config.n_experts)
        )
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, config.vocab_size, (B, T))
        with torch.no_grad():
            ref = m(torch.tensor(tokens)).logits.numpy()
        ours = llama.forward(params, jnp.asarray(tokens), config)
        np.testing.assert_allclose(np.asarray(ours), ref, rtol=1e-3, atol=5e-4)

    def test_llama4_chunked_attention_bites(self, tmp_path):
        """The chunk mask actually changes logits vs full attention
        (T=16 spans two 8-token chunks)."""
        self._tiny(tmp_path)
        config, params = load_checkpoint(str(tmp_path), dtype=jnp.float32)
        params = jax.device_put(params)
        config = llama.dataclasses.replace(
            config, remat=False, capacity_factor=float(config.n_experts)
        )
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(0, config.vocab_size, (B, T)))
        chunked = llama.forward(params, tokens, config)
        full = llama.forward(
            params, tokens,
            llama.dataclasses.replace(config, attention_chunk_size=0),
        )
        assert not np.allclose(np.asarray(chunked), np.asarray(full))

    def test_llama4_greedy_decode(self, tmp_path):
        m = self._tiny(tmp_path)
        config, params = load_checkpoint(str(tmp_path), dtype=jnp.float32)
        params = jax.device_put(params)
        config = llama.dataclasses.replace(
            config, remat=False, capacity_factor=float(config.n_experts)
        )
        from dstack_tpu.serve.engine import decode_step, init_cache, prefill

        rng = np.random.default_rng(1)
        prompt = rng.integers(1, config.vocab_size, (1, 12))
        n_new = 8
        with torch.no_grad():
            hf_out = m.generate(
                torch.tensor(prompt), max_new_tokens=n_new, do_sample=False,
                eos_token_id=None, pad_token_id=0,
            ).numpy()[0, prompt.shape[1]:]
        cache = init_cache(config, max_batch=1, max_seq=32)
        logits, cache = prefill(
            params, jnp.asarray(prompt), jnp.asarray([prompt.shape[1]]),
            jnp.asarray(0), config, cache,
        )
        out = []
        pos = prompt.shape[1]
        for _ in range(n_new):
            nxt = jnp.argmax(logits[0]).astype(jnp.int32)
            out.append(int(nxt))
            logits, cache = decode_step(
                params, cache, jnp.asarray([nxt]), jnp.asarray([pos]), config
            )
            pos += 1
        assert out == hf_out.tolist()

    def test_llama4_all_nope_layout(self):
        """no_rope_layers all zeros → every layer NoPE (pattern 1 must
        not invert back to rope-everywhere)."""
        from dstack_tpu.models.convert_hf import config_from_hf

        cfg = config_from_hf({
            "model_type": "llama4_text", "vocab_size": 128,
            "hidden_size": 64, "intermediate_size": 96,
            "num_hidden_layers": 3, "num_attention_heads": 4,
            "num_key_value_heads": 2, "num_local_experts": 4,
            "no_rope_layers": [0, 0, 0],
        })
        assert cfg.nope_pattern == 1
        assert llama.layer_nope(cfg) == [True, True, True]

    def test_llama4_interleaved_moe_rejected(self):
        from dstack_tpu.models.convert_hf import config_from_hf

        with pytest.raises(ValueError, match="interleave"):
            config_from_hf({
                "model_type": "llama4_text", "vocab_size": 128,
                "hidden_size": 64, "intermediate_size": 96,
                "num_hidden_layers": 4, "num_attention_heads": 4,
                "num_key_value_heads": 2, "num_local_experts": 4,
                "interleave_moe_layer_step": 2,
            })


class TestEngineParity:
    """KV-cache decode (prefill + decode_step) vs HF greedy generation.

    One family per engine-relevant delta group: gemma2 (norm offset,
    sandwich norms, softcaps, alternating windows, embed scale), qwen2
    (qkv bias), mixtral (MoE decode) — a flag ported to llama.forward
    but missed in the engine fails here."""

    def _assert_greedy_parity(self, tmp_path, hf_model, replace_cfg=None):
        config, params = load_checkpoint(str(tmp_path), dtype=jnp.float32)
        params = jax.device_put(params)
        config = llama.dataclasses.replace(
            config, remat=False, **(replace_cfg or {})
        )
        from dstack_tpu.serve.engine import decode_step, init_cache, prefill

        rng = np.random.default_rng(1)
        prompt = rng.integers(1, config.vocab_size, (1, 12))
        n_new = 8
        with torch.no_grad():
            hf_out = hf_model.generate(
                torch.tensor(prompt), max_new_tokens=n_new, do_sample=False,
                # tiny random models have no real eos; decode a fixed count
                eos_token_id=None, pad_token_id=0,
            ).numpy()[0, prompt.shape[1]:]

        cache = init_cache(config, max_batch=1, max_seq=32)
        logits, cache = prefill(
            params, jnp.asarray(prompt), jnp.asarray([prompt.shape[1]]),
            jnp.asarray(0), config, cache,
        )
        out = []
        pos = prompt.shape[1]
        for _ in range(n_new):
            nxt = jnp.argmax(logits[0]).astype(jnp.int32)
            out.append(int(nxt))
            logits, cache = decode_step(
                params, cache, jnp.asarray([nxt]), jnp.asarray([pos]), config
            )
            pos += 1
        assert out == hf_out.tolist()

    def test_gemma2_greedy_decode(self, tmp_path):
        m = _save_tiny(
            tmp_path, transformers.Gemma2Config, transformers.Gemma2ForCausalLM,
            head_dim=16, sliding_window=8,
            attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
            query_pre_attn_scalar=16,
        )
        self._assert_greedy_parity(tmp_path, m)

    def test_qwen2_greedy_decode(self, tmp_path):
        m = _save_tiny(
            tmp_path, transformers.Qwen2Config, transformers.Qwen2ForCausalLM,
        )
        self._assert_greedy_parity(tmp_path, m)

    def test_qwen3_greedy_decode(self, tmp_path):
        m = _save_tiny(
            tmp_path, transformers.Qwen3Config, transformers.Qwen3ForCausalLM,
            head_dim=16,
        )
        self._assert_greedy_parity(tmp_path, m)

    def test_gemma3_greedy_decode(self, tmp_path):
        """Engine decode path: traced-window dual-rope selection inside
        the layer scan + offset qk-norm must match HF generation."""
        m = _save_tiny(
            tmp_path, transformers.Gemma3TextConfig,
            transformers.Gemma3ForCausalLM,
            head_dim=16, sliding_window=8,
            layer_types=[
                "sliding_attention", "full_attention",
                "sliding_attention", "full_attention",
            ],
            rope_theta=1000000.0, rope_local_base_freq=10000.0,
            query_pre_attn_scalar=16,
        )
        self._assert_greedy_parity(tmp_path, m)

    def test_mixtral_greedy_decode(self, tmp_path):
        m = _save_tiny(
            tmp_path, transformers.MixtralConfig, transformers.MixtralForCausalLM,
            num_local_experts=4, num_experts_per_tok=2,
        )
        # no-drop capacity: static dispatch exact vs HF dynamic gather
        self._assert_greedy_parity(
            tmp_path, m, replace_cfg={"capacity_factor": 4.0}
        )


class TestExport:
    """Round trip: our params → HF directory → transformers forward
    must match our forward (the inverse converter is exact up to bf16)."""

    @pytest.mark.parametrize("family_kw", [
        {},  # llama
        {"qk_norm_family": True},  # qwen3
    ])
    def test_roundtrip_through_transformers(self, tmp_path, family_kw):
        from dstack_tpu.models.convert_hf import save_checkpoint

        if family_kw.get("qk_norm_family"):
            config = llama.LlamaConfig(
                vocab_size=128, hidden_size=64, n_layers=2, n_heads=4,
                n_kv_heads=2, head_dim=16, intermediate_size=96,
                rope_theta=10000.0, max_seq_len=64, dtype=jnp.float32,
                remat=False, qk_norm=True,
            )
        else:
            config = llama.LlamaConfig(
                vocab_size=128, hidden_size=64, n_layers=2, n_heads=4,
                n_kv_heads=2, head_dim=16, intermediate_size=96,
                rope_theta=10000.0, max_seq_len=64, dtype=jnp.float32,
                remat=False,
            )
        params = init_params(config, 0)
        out_dir = tmp_path / "export"
        save_checkpoint(config, params, str(out_dir))

        hf_model = transformers.AutoModelForCausalLM.from_pretrained(
            str(out_dir), torch_dtype=torch.float32
        )
        hf_model.eval()
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, config.vocab_size, (2, 12))
        with torch.no_grad():
            ref = hf_model(torch.tensor(tokens)).logits.numpy()
        ours = llama.forward(params, jnp.asarray(tokens), config)
        # bf16 storage rounds the weights once
        np.testing.assert_allclose(np.asarray(ours), ref, rtol=0.05, atol=0.05)

    def test_reload_with_our_loader(self, tmp_path):
        from dstack_tpu.models.convert_hf import load_checkpoint, save_checkpoint

        config = llama.dataclasses.replace(
            llama.LLAMA_TINY, vocab_size=300, tie_embeddings=False
        )
        params = init_params(config, 1)
        save_checkpoint(config, params, str(tmp_path / "rt"))
        config2, params2 = load_checkpoint(
            str(tmp_path / "rt"), dtype=jnp.float32
        )
        assert config2.n_layers == config.n_layers
        rng = np.random.default_rng(2)
        tokens = jnp.asarray(rng.integers(0, 300, (1, 16)))
        a = llama.forward(params, tokens, config)
        b = llama.forward(
            jax.device_put(params2), tokens,
            llama.dataclasses.replace(config2, remat=False),
        )
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0.05, atol=0.05
        )


class TestConfigRoundTrip:
    """config_to_hf ∘ config_from_hf preserves every family's
    architecture flags — the export a fine-tune writes must reload as
    the same model."""

    @pytest.mark.parametrize("name", [
        "llama-3.2-1b", "qwen-2.5-7b", "qwen-3-8b", "qwen-3-30b-a3b",
        "mistral-7b", "gemma-2b", "gemma-2-2b", "gemma-3-1b",
        "gemma-3-4b", "mixtral-8x7b", "llama-4-scout",
        "deepseek-v2-lite", "deepseek-v3", "glm-4-9b", "olmo-2-7b",
        "command-r-35b", "minitron-4b", "starcoder2-7b",
    ])
    def test_flags_survive(self, name):
        from dstack_tpu.models.convert_hf import config_from_hf, config_to_hf

        c = llama.CONFIGS[name]
        c2 = config_from_hf(config_to_hf(c), dtype=c.dtype)
        for field in (
            "vocab_size", "hidden_size", "n_layers", "n_heads",
            "intermediate_size", "rope_theta",
            "tie_embeddings", "qkv_bias", "qk_norm", "sliding_window",
            "sliding_pattern", "hidden_act", "norm_offset", "embed_scale",
            "post_norms", "attn_softcap", "logit_softcap", "n_experts",
            "experts_per_token", "rope_scaling", "rope_local_theta",
            "nope_pattern", "rope_interleaved", "qk_l2_norm",
            "attention_chunk_size", "attn_temp_scale", "attn_temp_floor",
            "router_sigmoid_input", "moe_shared_expert",
            "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "router_score",
            "router_bias", "router_groups", "routed_scale",
            "moe_shared_intermediate", "first_k_dense",
            "dense_intermediate", "partial_rotary", "pre_norm",
            "qk_norm_flat", "norm_type", "parallel_block", "logit_scale",
            "mlp_gateless", "proj_bias",
        ):
            assert getattr(c2, field) == getattr(c, field), (name, field)
        if not c.mla:  # under MLA head_dim/n_kv_heads are unused
            for field in ("n_kv_heads", "head_dim"):
                assert getattr(c2, field) == getattr(c, field), (name, field)
        if c.attn_scale is not None:
            assert abs(c2.attn_scale - c.attn_scale) < 1e-9

    def test_unknown_model_type_rejected(self):
        from dstack_tpu.models.convert_hf import config_from_hf

        with pytest.raises(ValueError, match="model_type"):
            config_from_hf({
                "model_type": "mamba", "hidden_size": 8,
                "num_attention_heads": 2, "vocab_size": 16,
                "num_hidden_layers": 1, "intermediate_size": 16,
            })


class TestQwen3Moe:
    def test_qwen3_moe_logit_parity(self, tmp_path):
        """qwen3 attention (qk-norm) + sparse MoE MLP: router renorm,
        per-expert gate/up/down naming, moe_intermediate_size."""
        m = _save_tiny(
            tmp_path,
            transformers.Qwen3MoeConfig,
            transformers.Qwen3MoeForCausalLM,
            num_experts=4,
            num_experts_per_tok=2,
            moe_intermediate_size=96,
            norm_topk_prob=True,
            decoder_sparse_step=1,
            mlp_only_layers=[],
            head_dim=16,
        )
        config, params = load_checkpoint(str(tmp_path), dtype=jnp.float32)
        assert config.qk_norm and config.n_experts == 4 and config.router_renorm
        params = jax.device_put(params)
        config = llama.dataclasses.replace(
            config, remat=False, capacity_factor=float(config.n_experts)
        )
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, config.vocab_size, (B, T))
        with torch.no_grad():
            ref = m(torch.tensor(tokens)).logits.numpy()
        ours = llama.forward(params, jnp.asarray(tokens), config)
        np.testing.assert_allclose(np.asarray(ours), ref, rtol=1e-3, atol=5e-4)

    def test_cohere_parallel_block(self, tmp_path):
        """Command-R: mean-centered LayerNorm, parallel attn+MLP over
        one shared input norm, interleaved rope, logit_scale."""
        m = _save_tiny(
            tmp_path, transformers.CohereConfig, transformers.CohereForCausalLM,
            logit_scale=0.0625, use_qk_norm=False, pad_token_id=0,
        )
        cfg = _assert_parity(tmp_path, m)
        assert cfg.parallel_block and cfg.norm_type == "layernorm"
        assert cfg.logit_scale == 0.0625 and cfg.tie_embeddings
        assert cfg.rope_interleaved and not cfg.qk_norm

    def test_cohere_qk_norm(self, tmp_path):
        """Command-R+ adds per-head q/k LayerNorm ([H, D] weights,
        applied before rope)."""
        m = _save_tiny(
            tmp_path, transformers.CohereConfig, transformers.CohereForCausalLM,
            logit_scale=0.0625, use_qk_norm=True, pad_token_id=0,
        )
        cfg = _assert_parity(tmp_path, m)
        assert cfg.qk_norm and cfg.norm_type == "layernorm"

    def test_cohere_greedy_decode(self, tmp_path):
        m = _save_tiny(
            tmp_path, transformers.CohereConfig, transformers.CohereForCausalLM,
            logit_scale=0.0625, use_qk_norm=True, pad_token_id=0,
        )
        config, params = load_checkpoint(str(tmp_path), dtype=jnp.float32)
        params = jax.device_put(params)
        config = llama.dataclasses.replace(config, remat=False)
        from dstack_tpu.serve.engine import GenParams, InferenceEngine

        eng = InferenceEngine(
            config, params, max_batch=2, max_seq=48,
            spec_draft=0, turbo_steps=0,
        )
        prompt = [5, 9, 21, 7]
        out = eng.generate(prompt, GenParams(max_new_tokens=6, temperature=0.0))
        seq = list(prompt)
        ref = []
        for _ in range(6):
            logits = llama.forward(params, jnp.asarray([seq], jnp.int32), config)
            nxt = int(jnp.argmax(logits[0, -1]))
            ref.append(nxt)
            seq.append(nxt)
        assert out == ref

    def test_olmo2_post_norm_layout(self, tmp_path):
        """OLMo-2: NO pre-norms (sublayer outputs normed before the
        residual add) and q/k RMSNorm over the full projection width
        before the head reshape."""
        m = _save_tiny(
            tmp_path, transformers.Olmo2Config, transformers.Olmo2ForCausalLM,
        )
        cfg = _assert_parity(tmp_path, m)
        assert not cfg.pre_norm and cfg.post_norms and cfg.qk_norm_flat

    def test_olmo2_greedy_decode(self, tmp_path):
        m = _save_tiny(
            tmp_path, transformers.Olmo2Config, transformers.Olmo2ForCausalLM,
        )
        config, params = load_checkpoint(str(tmp_path), dtype=jnp.float32)
        params = jax.device_put(params)
        config = llama.dataclasses.replace(config, remat=False)
        from dstack_tpu.serve.engine import GenParams, InferenceEngine

        eng = InferenceEngine(
            config, params, max_batch=2, max_seq=48,
            spec_draft=0, turbo_steps=0,
        )
        prompt = [5, 9, 21, 7]
        out = eng.generate(prompt, GenParams(max_new_tokens=6, temperature=0.0))
        seq = list(prompt)
        ref = []
        for _ in range(6):
            logits = llama.forward(params, jnp.asarray([seq], jnp.int32), config)
            nxt = int(jnp.argmax(logits[0, -1]))
            ref.append(nxt)
            seq.append(nxt)
        assert out == ref

    def test_olmo2_export_roundtrip(self, tmp_path):
        """save_checkpoint(olmo2) → transformers loads it and agrees."""
        from dstack_tpu.models.convert_hf import save_checkpoint

        config = llama.dataclasses.replace(
            llama.OLMO2_7B, vocab_size=128, hidden_size=64, n_layers=2,
            n_heads=4, n_kv_heads=2, head_dim=16, intermediate_size=96,
            max_seq_len=64, dtype=jnp.float32, remat=False,
        )
        params = init_params(config, 0)
        out = tmp_path / "export"
        save_checkpoint(config, params, str(out))
        hf_model = transformers.AutoModelForCausalLM.from_pretrained(
            str(out), torch_dtype=torch.float32
        )
        hf_model.eval()
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, config.vocab_size, (2, 12))
        with torch.no_grad():
            ref = hf_model(torch.tensor(tokens)).logits.numpy()
        ours = llama.forward(params, jnp.asarray(tokens), config)
        np.testing.assert_allclose(np.asarray(ours), ref, rtol=0.05, atol=0.05)

    def test_glm_partial_rotary(self, tmp_path):
        """GLM: interleaved rope on the first half of head_dim only,
        qkv bias, fused gate_up MLP split on load."""
        m = _save_tiny(
            tmp_path, transformers.GlmConfig, transformers.GlmForCausalLM,
            head_dim=16, partial_rotary_factor=0.5, pad_token_id=0,
        )
        cfg = _assert_parity(tmp_path, m)
        assert cfg.partial_rotary == 0.5 and cfg.qkv_bias
        assert cfg.rope_interleaved and not cfg.post_norms
        assert cfg.rope_dim == 8
        # bias-free GLM round-trips without resurrecting the bias
        from dstack_tpu.models.convert_hf import config_from_hf, config_to_hf

        c2 = config_from_hf(
            config_to_hf(llama.dataclasses.replace(cfg, qkv_bias=False))
        )
        assert not c2.qkv_bias and c2.partial_rotary == 0.5

    def test_glm4_sandwich_norms(self, tmp_path):
        """glm4 adds post_self_attn/post_mlp sandwich norms on top of
        the GLM layout — mapped onto the post_norms flag with renames."""
        m = _save_tiny(
            tmp_path, transformers.Glm4Config, transformers.Glm4ForCausalLM,
            head_dim=16, partial_rotary_factor=0.5, pad_token_id=0,
        )
        cfg = _assert_parity(tmp_path, m)
        assert cfg.post_norms and cfg.partial_rotary == 0.5

    def test_glm4_greedy_decode(self, tmp_path):
        """Engine decode parity for partial rotary: the narrow cos/sin
        must rotate only the leading dims in decode/prefill too."""
        m = _save_tiny(
            tmp_path, transformers.Glm4Config, transformers.Glm4ForCausalLM,
            head_dim=16, partial_rotary_factor=0.5, pad_token_id=0,
        )
        config, params = load_checkpoint(str(tmp_path), dtype=jnp.float32)
        params = jax.device_put(params)
        config = llama.dataclasses.replace(config, remat=False)
        from dstack_tpu.serve.engine import GenParams, InferenceEngine

        eng = InferenceEngine(
            config, params, max_batch=2, max_seq=48,
            spec_draft=0, turbo_steps=0,
        )
        prompt = [5, 9, 21, 7]
        out = eng.generate(prompt, GenParams(max_new_tokens=6, temperature=0.0))
        seq = list(prompt)
        ref = []
        for _ in range(6):
            logits = llama.forward(params, jnp.asarray([seq], jnp.int32), config)
            nxt = int(jnp.argmax(logits[0, -1]))
            ref.append(nxt)
            seq.append(nxt)
        assert out == ref

    def test_deepseek_v2_mscale_flag(self, monkeypatch):
        """V2-Lite attention-scale policy: default follows HF's native
        DeepseekV2Attention (no mscale^2 correction — attn_scale unset);
        DTPU_DEEPSEEK_V2_MSCALE_FIX=1 applies the released model's
        remote-code correction; V3 always applies it (VERDICT r4 #6)."""
        import math

        from dstack_tpu.models.convert_hf import config_from_hf

        def v2_lite(model_type):
            # the fields _deepseek_config reads, V2-Lite values where it
            # matters (mscale_all_dim=0.707, yarn factor=40)
            return {
                "model_type": model_type,
                "hidden_size": 128, "num_attention_heads": 4,
                "num_hidden_layers": 2, "num_key_value_heads": 4,
                "intermediate_size": 256, "vocab_size": 128,
                "rms_norm_eps": 1e-6, "max_position_embeddings": 163840,
                "rope_theta": 10000.0,
                "q_lora_rank": None, "kv_lora_rank": 32,
                "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
                "v_head_dim": 24, "head_dim": 16,
                "first_k_dense_replace": 2,
                "rope_scaling": {
                    "rope_type": "yarn", "factor": 40.0,
                    "mscale": 0.707, "mscale_all_dim": 0.707,
                    "original_max_position_embeddings": 4096,
                    "beta_fast": 32, "beta_slow": 1,
                },
                # V3-only router fields (ignored by the dense-only path)
                "n_group": 1, "topk_group": 1,
            }

        monkeypatch.delenv("DTPU_DEEPSEEK_V2_MSCALE_FIX", raising=False)
        assert config_from_hf(v2_lite("deepseek_v2")).attn_scale is None

        ms = 0.1 * 0.707 * math.log(40.0) + 1.0
        expected = 48 ** -0.5 * ms * ms  # qk_dim = 32 nope + 16 rope
        monkeypatch.setenv("DTPU_DEEPSEEK_V2_MSCALE_FIX", "1")
        fixed = config_from_hf(v2_lite("deepseek_v2")).attn_scale
        assert fixed == pytest.approx(expected)
        # the correction is the documented ~1.59x over the HF default
        assert fixed / 48 ** -0.5 == pytest.approx(ms * ms, rel=1e-6)
        assert ms * ms == pytest.approx(1.59, abs=5e-3)

        monkeypatch.delenv("DTPU_DEEPSEEK_V2_MSCALE_FIX", raising=False)
        v3 = config_from_hf(v2_lite("deepseek_v3")).attn_scale
        assert v3 == pytest.approx(expected)  # V3 applies it always

    def test_deepseek_v2_mla_dense(self, tmp_path):
        """MLA attention alone (every layer dense): latent kv projection,
        split nope/rope head dims, shared single-head rope key, own v
        head dim, interleaved-complex rope on the pe slices."""
        m = _save_tiny(
            tmp_path,
            transformers.DeepseekV2Config,
            transformers.DeepseekV2ForCausalLM,
            num_key_value_heads=4,
            first_k_dense_replace=4,  # = num_hidden_layers: no MoE layer
            q_lora_rank=None,  # V2-Lite style direct q projection
            kv_lora_rank=32,
            qk_nope_head_dim=32,
            qk_rope_head_dim=16,
            v_head_dim=24,
            head_dim=16,  # HF derives the rope dim from this
        )
        cfg = _assert_parity(tmp_path, m)
        assert cfg.mla and cfg.q_lora_rank == 0 and cfg.n_experts == 0
        assert cfg.qk_head_dim == 48 and cfg.v_head_dim == 24

    def test_deepseek_v2_q_lora(self, tmp_path):
        """Full-size V2 shape: low-rank q projection (q_a/q_b + norm)."""
        m = _save_tiny(
            tmp_path,
            transformers.DeepseekV2Config,
            transformers.DeepseekV2ForCausalLM,
            num_key_value_heads=4,
            first_k_dense_replace=4,
            q_lora_rank=48,
            kv_lora_rank=32,
            qk_nope_head_dim=32,
            qk_rope_head_dim=16,
            v_head_dim=24,
            head_dim=16,
        )
        cfg = _assert_parity(tmp_path, m)
        assert cfg.q_lora_rank == 48

    def test_deepseek_v2_moe(self, tmp_path):
        """V2 MoE: softmax full-score gates, dense first-k prelude,
        fused shared experts, greedy top-k."""
        m = _save_tiny(
            tmp_path,
            transformers.DeepseekV2Config,
            transformers.DeepseekV2ForCausalLM,
            num_key_value_heads=4,
            first_k_dense_replace=1,
            q_lora_rank=None,
            kv_lora_rank=32,
            qk_nope_head_dim=32,
            qk_rope_head_dim=16,
            v_head_dim=24,
            head_dim=16,
            n_routed_experts=8,
            n_shared_experts=2,
            num_experts_per_tok=3,
            moe_intermediate_size=32,
            topk_method="greedy",
            norm_topk_prob=False,
            routed_scaling_factor=1.0,
            n_group=1,
            topk_group=1,
        )
        config, params = load_checkpoint(str(tmp_path), dtype=jnp.float32)
        assert config.first_k_dense == 1 and config.n_experts == 8
        assert config.moe_shared_expert
        assert config.moe_shared_intermediate == 64  # 2 shared × 32
        assert config.dense_intermediate == 96
        params = jax.device_put(params)
        config = llama.dataclasses.replace(
            config, remat=False, capacity_factor=float(config.n_experts)
        )
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, config.vocab_size, (B, T))
        with torch.no_grad():
            ref = m(torch.tensor(tokens)).logits.numpy()
        ours = llama.forward(params, jnp.asarray(tokens), config)
        np.testing.assert_allclose(np.asarray(ours), ref, rtol=1e-3, atol=5e-4)

    def test_deepseek_v2_group_limited(self, tmp_path):
        """V2 group_limited_greedy: only the best topk_group expert
        groups (scored by their best member) are selectable."""
        m = _save_tiny(
            tmp_path,
            transformers.DeepseekV2Config,
            transformers.DeepseekV2ForCausalLM,
            num_key_value_heads=4,
            first_k_dense_replace=1,
            q_lora_rank=None,
            kv_lora_rank=32,
            qk_nope_head_dim=32,
            qk_rope_head_dim=16,
            v_head_dim=24,
            head_dim=16,
            n_routed_experts=8,
            n_shared_experts=1,
            num_experts_per_tok=2,
            moe_intermediate_size=32,
            topk_method="group_limited_greedy",
            n_group=4,
            topk_group=2,
            norm_topk_prob=False,
            routed_scaling_factor=1.5,
        )
        config, params = load_checkpoint(str(tmp_path), dtype=jnp.float32)
        assert config.router_groups == (4, 2) and config.routed_scale == 1.5
        params = jax.device_put(params)
        config = llama.dataclasses.replace(
            config, remat=False, capacity_factor=float(config.n_experts)
        )
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, config.vocab_size, (B, T))
        with torch.no_grad():
            ref = m(torch.tensor(tokens)).logits.numpy()
        ours = llama.forward(params, jnp.asarray(tokens), config)
        np.testing.assert_allclose(np.asarray(ours), ref, rtol=1e-3, atol=5e-4)

    def test_deepseek_v3(self, tmp_path):
        """V3: sigmoid scoring, e_score_correction_bias (selection
        only), group top-2-sum limiting, renormed gates × routed
        scale."""
        m = _save_tiny(
            tmp_path,
            transformers.DeepseekV3Config,
            transformers.DeepseekV3ForCausalLM,
            num_key_value_heads=4,
            first_k_dense_replace=1,
            q_lora_rank=48,
            kv_lora_rank=32,
            qk_nope_head_dim=32,
            qk_rope_head_dim=16,
            v_head_dim=24,
            head_dim=16,
            n_routed_experts=8,
            n_shared_experts=1,
            num_experts_per_tok=3,
            moe_intermediate_size=32,
            n_group=4,
            topk_group=2,
            norm_topk_prob=True,
            routed_scaling_factor=2.5,
        )
        # exercise the correction bias: the random init leaves it zero.
        # Std 0.1 dominates the (near-0.5) sigmoid score spread so the
        # bias demonstrably drives selection, while keeping every biased
        # score positive — a tiny random model with larger biases can
        # push a whole group below the masked-fill zeros, creating an
        # exact top-k TIE whose torch-vs-jax tie-breaking diverges
        # (never happens with trained checkpoints' score scales).
        with torch.no_grad():
            for lyr in m.model.layers[1:]:
                lyr.mlp.gate.e_score_correction_bias.normal_(0.0, 0.1)
        m.save_pretrained(tmp_path)
        config, params = load_checkpoint(str(tmp_path), dtype=jnp.float32)
        assert config.router_score == "sigmoid" and config.router_bias
        assert config.router_groups == (4, 2) and config.router_renorm
        assert float(np.abs(params["layers"]["router_bias"]).max()) > 0
        params = jax.device_put(params)
        config = llama.dataclasses.replace(
            config, remat=False, capacity_factor=float(config.n_experts)
        )
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, config.vocab_size, (B, T))
        with torch.no_grad():
            ref = m(torch.tensor(tokens)).logits.numpy()
        ours = llama.forward(params, jnp.asarray(tokens), config)
        np.testing.assert_allclose(np.asarray(ours), ref, rtol=1e-3, atol=5e-4)

    def test_deepseek_v3_yarn_mscale(self, tmp_path):
        """V3 under yarn multiplies the softmax scale by
        mscale(factor, mscale_all_dim)^2 — V2 does not; missing it makes
        attention logits ~1.9x too small on real V3 checkpoints."""
        m = _save_tiny(
            tmp_path,
            transformers.DeepseekV3Config,
            transformers.DeepseekV3ForCausalLM,
            num_key_value_heads=4,
            first_k_dense_replace=4,
            q_lora_rank=48,
            kv_lora_rank=32,
            qk_nope_head_dim=32,
            qk_rope_head_dim=16,
            v_head_dim=24,
            head_dim=16,
            rope_scaling={
                "rope_type": "yarn", "factor": 40.0,
                "beta_fast": 32.0, "beta_slow": 1.0,
                "mscale": 1.0, "mscale_all_dim": 1.0,
                "original_max_position_embeddings": 8,
            },
        )
        cfg = _assert_parity(tmp_path, m)
        import math as _math

        expected = (48.0**-0.5) * (0.1 * _math.log(40.0) + 1.0) ** 2
        assert cfg.attn_scale is not None
        assert abs(cfg.attn_scale - expected) < 1e-9

    def test_deepseek_yarn_rope(self, tmp_path):
        """YaRN NTK-by-parts rope (DeepSeek long-context checkpoints):
        must match HF and differ from unscaled rope."""
        m = _save_tiny(
            tmp_path,
            transformers.DeepseekV2Config,
            transformers.DeepseekV2ForCausalLM,
            num_key_value_heads=4,
            first_k_dense_replace=4,
            q_lora_rank=None,
            kv_lora_rank=32,
            qk_nope_head_dim=32,
            qk_rope_head_dim=16,
            v_head_dim=24,
            head_dim=16,
            rope_scaling={
                "rope_type": "yarn", "factor": 4.0,
                "beta_fast": 32.0, "beta_slow": 1.0,
                "mscale": 0.707, "mscale_all_dim": 0.707,
                "original_max_position_embeddings": 8,
            },
        )
        cfg = _assert_parity(tmp_path, m)
        assert cfg.rope_scaling is not None and cfg.rope_scaling[0] == "yarn"
        config, params = load_checkpoint(str(tmp_path), dtype=jnp.float32)
        params = jax.device_put(params)
        config = llama.dataclasses.replace(config, remat=False)
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(0, config.vocab_size, (B, T)))
        scaled = llama.forward(params, tokens, config)
        plain = llama.forward(
            params, tokens,
            llama.dataclasses.replace(config, rope_scaling=None),
        )
        assert not np.allclose(np.asarray(scaled), np.asarray(plain))

    def test_qwen3_moe_dense_layers_rejected(self, tmp_path):
        from dstack_tpu.models.convert_hf import config_from_hf

        with pytest.raises(ValueError, match="dense layers"):
            config_from_hf({
                "model_type": "qwen3_moe", "vocab_size": 128,
                "hidden_size": 64, "intermediate_size": 96,
                "moe_intermediate_size": 96, "num_hidden_layers": 4,
                "num_attention_heads": 4, "num_experts": 4,
                "mlp_only_layers": [0],
            })


class TestCohere2:
    def test_cohere2_sliding_nope_layout(self, tmp_path):
        """Command R7B: Cohere layout + periodic sliding where the
        full-attention layers carry NO rope (aligned NoPE)."""
        m = _save_tiny(
            tmp_path, transformers.Cohere2Config,
            transformers.Cohere2ForCausalLM,
            logit_scale=0.0625, pad_token_id=0, sliding_window=8,
            sliding_window_pattern=4,
            layer_types=["sliding_attention", "sliding_attention",
                         "sliding_attention", "full_attention"],
        )
        cfg = _assert_parity(tmp_path, m)
        assert cfg.parallel_block and cfg.norm_type == "layernorm"
        assert cfg.sliding_pattern == 4 and cfg.nope_pattern == 4
        assert llama.layer_windows(cfg) == [8, 8, 8, 0]
        assert llama.layer_nope(cfg) == [False, False, False, True]

    def test_cohere2_greedy_decode(self, tmp_path):
        m = _save_tiny(
            tmp_path, transformers.Cohere2Config,
            transformers.Cohere2ForCausalLM,
            logit_scale=0.0625, pad_token_id=0, sliding_window=8,
            sliding_window_pattern=4,
            layer_types=["sliding_attention", "sliding_attention",
                         "sliding_attention", "full_attention"],
        )
        config, params = load_checkpoint(str(tmp_path), dtype=jnp.float32)
        params = jax.device_put(params)
        config = llama.dataclasses.replace(config, remat=False)
        from dstack_tpu.serve.engine import GenParams, InferenceEngine

        eng = InferenceEngine(
            config, params, max_batch=2, max_seq=48,
            spec_draft=0, turbo_steps=0,
        )
        prompt = [5, 9, 21, 7, 3, 2, 8, 1, 4, 6, 11, 13]  # spans the window
        out = eng.generate(prompt, GenParams(max_new_tokens=6, temperature=0.0))
        seq = list(prompt)
        ref = []
        for _ in range(6):
            logits = llama.forward(params, jnp.asarray([seq], jnp.int32), config)
            nxt = int(jnp.argmax(logits[0, -1]))
            ref.append(nxt)
            seq.append(nxt)
        assert out == ref

    def test_cohere2_config_roundtrip(self):
        from dstack_tpu.models.convert_hf import config_from_hf, config_to_hf

        c = llama.LlamaConfig(
            vocab_size=256, hidden_size=64, n_layers=8, n_heads=4,
            n_kv_heads=2, head_dim=16, intermediate_size=96,
            norm_eps=1e-5, tie_embeddings=True, norm_type="layernorm",
            parallel_block=True, rope_interleaved=True, logit_scale=0.0625,
            sliding_window=8, sliding_pattern=4, nope_pattern=4,
        )
        c2 = config_from_hf(config_to_hf(c), dtype=c.dtype)
        for f in ("sliding_window", "sliding_pattern", "nope_pattern",
                  "parallel_block", "norm_type", "logit_scale"):
            assert getattr(c2, f) == getattr(c, f), f


class TestStarcoder2:
    def test_starcoder2_layout(self, tmp_path):
        """StarCoder2: plain LayerNorm WITH bias (stacked storage),
        biases on every projection, gateless GELU MLP (c_fc/c_proj)."""
        m = _save_tiny(
            tmp_path, transformers.Starcoder2Config,
            transformers.Starcoder2ForCausalLM,
            sliding_window=None, use_bias=True,
        )
        cfg = _assert_parity(tmp_path, m)
        assert cfg.norm_type == "layernorm_bias" and cfg.mlp_gateless
        assert cfg.qkv_bias and cfg.proj_bias and cfg.tie_embeddings
        assert cfg.hidden_act == "gelu_tanh"

    def test_starcoder2_greedy_decode(self, tmp_path):
        m = _save_tiny(
            tmp_path, transformers.Starcoder2Config,
            transformers.Starcoder2ForCausalLM,
            sliding_window=None, use_bias=True,
        )
        config, params = load_checkpoint(str(tmp_path), dtype=jnp.float32)
        params = jax.device_put(params)
        config = llama.dataclasses.replace(config, remat=False)
        from dstack_tpu.serve.engine import GenParams, InferenceEngine

        eng = InferenceEngine(
            config, params, max_batch=2, max_seq=48,
            spec_draft=0, turbo_steps=0,
        )
        prompt = [5, 9, 21, 7]
        out = eng.generate(prompt, GenParams(max_new_tokens=6, temperature=0.0))
        seq = list(prompt)
        ref = []
        for _ in range(6):
            logits = llama.forward(params, jnp.asarray([seq], jnp.int32), config)
            nxt = int(jnp.argmax(logits[0, -1]))
            ref.append(nxt)
            seq.append(nxt)
        assert out == ref

    def test_starcoder2_export_roundtrip(self, tmp_path):
        from dstack_tpu.models.convert_hf import save_checkpoint

        config = llama.dataclasses.replace(
            llama.STARCODER2_7B, vocab_size=128, hidden_size=64, n_layers=2,
            n_heads=4, n_kv_heads=2, head_dim=16, intermediate_size=96,
            max_seq_len=64, sliding_window=0, dtype=jnp.float32, remat=False,
        )
        params = init_params(config, 0)
        out = tmp_path / "export"
        save_checkpoint(config, params, str(out))
        hf_model = transformers.AutoModelForCausalLM.from_pretrained(
            str(out), torch_dtype=torch.float32
        )
        hf_model.eval()
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, config.vocab_size, (2, 12))
        with torch.no_grad():
            ref = hf_model(torch.tensor(tokens)).logits.numpy()
        ours = llama.forward(params, jnp.asarray(tokens), config)
        np.testing.assert_allclose(np.asarray(ours), ref, rtol=0.05, atol=0.05)


class TestNemotron:
    def test_nemotron_layout(self, tmp_path):
        """Nemotron/Minitron: LayerNorm1P ((1+w)·norm + bias, stacked
        storage), gateless relu² MLP, rotate-half partial rotary."""
        m = _save_tiny(
            tmp_path, transformers.NemotronConfig,
            transformers.NemotronForCausalLM,
            partial_rotary_factor=0.5, head_dim=16,
        )
        cfg = _assert_parity(tmp_path, m)
        assert cfg.norm_type == "layernorm1p" and cfg.mlp_gateless
        assert cfg.hidden_act == "relu2" and cfg.partial_rotary == 0.5
        assert not cfg.rope_interleaved

    def test_nemotron_greedy_decode(self, tmp_path):
        m = _save_tiny(
            tmp_path, transformers.NemotronConfig,
            transformers.NemotronForCausalLM,
            partial_rotary_factor=0.5, head_dim=16,
        )
        config, params = load_checkpoint(str(tmp_path), dtype=jnp.float32)
        params = jax.device_put(params)
        config = llama.dataclasses.replace(config, remat=False)
        from dstack_tpu.serve.engine import GenParams, InferenceEngine

        eng = InferenceEngine(
            config, params, max_batch=2, max_seq=48,
            spec_draft=0, turbo_steps=0,
        )
        prompt = [5, 9, 21, 7]
        out = eng.generate(prompt, GenParams(max_new_tokens=6, temperature=0.0))
        seq = list(prompt)
        ref = []
        for _ in range(6):
            logits = llama.forward(params, jnp.asarray([seq], jnp.int32), config)
            nxt = int(jnp.argmax(logits[0, -1]))
            ref.append(nxt)
            seq.append(nxt)
        assert out == ref

    def test_nemotron_export_roundtrip(self, tmp_path):
        from dstack_tpu.models.convert_hf import save_checkpoint

        config = llama.dataclasses.replace(
            llama.MINITRON_4B, vocab_size=128, hidden_size=64, n_layers=2,
            n_heads=4, n_kv_heads=2, head_dim=16, intermediate_size=96,
            max_seq_len=64, dtype=jnp.float32, remat=False,
        )
        params = init_params(config, 0)
        out = tmp_path / "export"
        save_checkpoint(config, params, str(out))
        hf_model = transformers.AutoModelForCausalLM.from_pretrained(
            str(out), torch_dtype=torch.float32
        )
        hf_model.eval()
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, config.vocab_size, (2, 12))
        with torch.no_grad():
            ref = hf_model(torch.tensor(tokens)).logits.numpy()
        ours = llama.forward(params, jnp.asarray(tokens), config)
        np.testing.assert_allclose(np.asarray(ours), ref, rtol=0.05, atol=0.05)


class TestGranite:
    def test_granite_multipliers(self, tmp_path):
        """IBM Granite: llama skeleton + embedding/residual/attention
        multipliers and logits_scaling (divisor)."""
        m = _save_tiny(
            tmp_path, transformers.GraniteConfig,
            transformers.GraniteForCausalLM,
            embedding_multiplier=12.0, residual_multiplier=0.22,
            attention_multiplier=0.015625, logits_scaling=8.0,
        )
        cfg = _assert_parity(tmp_path, m)
        assert cfg.embed_multiplier == 12.0
        assert cfg.residual_multiplier == 0.22
        assert cfg.attn_scale == 0.015625
        assert abs(cfg.logit_scale - 0.125) < 1e-12

    def test_granite_greedy_decode(self, tmp_path):
        m = _save_tiny(
            tmp_path, transformers.GraniteConfig,
            transformers.GraniteForCausalLM,
            embedding_multiplier=12.0, residual_multiplier=0.22,
            attention_multiplier=0.015625, logits_scaling=8.0,
        )
        config, params = load_checkpoint(str(tmp_path), dtype=jnp.float32)
        params = jax.device_put(params)
        config = llama.dataclasses.replace(config, remat=False)
        from dstack_tpu.serve.engine import GenParams, InferenceEngine

        eng = InferenceEngine(
            config, params, max_batch=2, max_seq=48,
            spec_draft=0, turbo_steps=0,
        )
        prompt = [5, 9, 21, 7]
        out = eng.generate(prompt, GenParams(max_new_tokens=6, temperature=0.0))
        seq = list(prompt)
        ref = []
        for _ in range(6):
            logits = llama.forward(params, jnp.asarray([seq], jnp.int32), config)
            nxt = int(jnp.argmax(logits[0, -1]))
            ref.append(nxt)
            seq.append(nxt)
        assert out == ref

    def test_granite_config_roundtrip(self):
        from dstack_tpu.models.convert_hf import config_from_hf, config_to_hf

        c = llama.LlamaConfig(
            vocab_size=256, hidden_size=64, n_layers=2, n_heads=4,
            n_kv_heads=2, head_dim=16, intermediate_size=96,
            embed_multiplier=12.0, residual_multiplier=0.22,
            attn_scale=0.015625, logit_scale=0.125,
        )
        c2 = config_from_hf(config_to_hf(c), dtype=c.dtype)
        for f in ("embed_multiplier", "residual_multiplier", "attn_scale",
                  "logit_scale"):
            assert abs(getattr(c2, f) - getattr(c, f)) < 1e-12, f


class TestGptOss:
    """OpenAI gpt-oss (HF modeling_gpt_oss): attention sinks, alternating
    sliding/full attention, linear router with softmax-over-top-k gates,
    fused biased experts with the clamped glu, yarn truncate=false."""

    def _tiny(self, tmp_path, **kw):
        return _save_tiny(
            tmp_path, transformers.GptOssConfig,
            transformers.GptOssForCausalLM,
            intermediate_size=64,
            head_dim=16,
            num_local_experts=4,
            num_experts_per_tok=2,
            sliding_window=8,  # < T so the sliding mask bites
            tie_word_embeddings=False,
            **kw,
        )

    def test_forward_parity(self, tmp_path):
        m = self._tiny(tmp_path)
        config, params = load_checkpoint(str(tmp_path), dtype=jnp.float32)
        assert config.attn_sinks and config.moe_bias
        assert config.router_topk_softmax and config.moe_act == "oai_glu"
        assert config.sliding_window == 8 and config.sliding_pattern == 2
        assert config.qkv_bias and config.proj_bias
        assert config.rope_scaling[0] == "yarn" and config.rope_scaling[6] is False
        params = jax.device_put(params)
        # capacity = n_experts: no token can be capacity-dropped, so the
        # static dispatch matches HF's dense scatter exactly
        config = llama.dataclasses.replace(
            config, remat=False, capacity_factor=float(config.n_experts)
        )
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, config.vocab_size, (B, T))
        with torch.no_grad():
            ref = m(torch.tensor(tokens)).logits.numpy()
        ours = llama.forward(params, jnp.asarray(tokens), config)
        np.testing.assert_allclose(np.asarray(ours), ref, rtol=1e-3, atol=5e-4)

    def test_sinks_actually_matter(self, tmp_path):
        """Pushing the learned sinks to a LARGE value (absorbing most
        probability mass) must change the logits — guards the sink
        plumbing against silently becoming a no-op. (Freshly-initialized
        tiny-model sinks sit near zero, so zeroing them would be too
        weak a probe.)"""
        self._tiny(tmp_path)
        config, params = load_checkpoint(str(tmp_path), dtype=jnp.float32)
        params = jax.device_put(params)
        config = llama.dataclasses.replace(
            config, remat=False, capacity_factor=float(config.n_experts)
        )
        rng = np.random.default_rng(1)
        tokens = jnp.asarray(rng.integers(0, config.vocab_size, (B, T)))
        base = llama.forward(params, tokens, config)
        big_sinks = dict(params)
        big_sinks["layers"] = {
            **params["layers"],
            "sinks": params["layers"]["sinks"] * 0.0 + 10.0,
        }
        moved = llama.forward(big_sinks, tokens, config)
        assert not np.allclose(np.asarray(base), np.asarray(moved), atol=1e-4)

    def test_engine_greedy_decode_matches_forward(self, tmp_path):
        """Serving path parity: chunked prefill + masked-cache decode
        (both carrying the sink column) reproduce the full forward's
        greedy tokens."""
        self._tiny(tmp_path)
        config, params = load_checkpoint(str(tmp_path), dtype=jnp.float32)
        params = jax.device_put(params)
        config = llama.dataclasses.replace(
            config, remat=False, capacity_factor=float(config.n_experts)
        )
        from dstack_tpu.serve.engine import GenParams, InferenceEngine

        eng = InferenceEngine(
            config, params, max_batch=2, max_seq=48,
            spec_draft=0, turbo_steps=0,
        )
        # repetitive prompt so the n-gram drafter actually forms drafts
        # and the SPECULATIVE verify path (which must carry the sink
        # column too) executes
        eng_spec = InferenceEngine(
            config, params, max_batch=2, max_seq=48,
            spec_draft=3, turbo_steps=0,
        )
        prompt = [3, 17, 9, 25, 6, 3, 17, 9, 25, 6]
        gp = GenParams(max_new_tokens=6, temperature=0.0)
        out = eng.generate(prompt, gp)
        out_spec = eng_spec.generate(prompt, gp)
        seq = list(prompt)
        ref = []
        for _ in range(6):
            logits = llama.forward(params, jnp.asarray([seq], jnp.int32), config)
            nxt = int(jnp.argmax(logits[0, -1]))
            ref.append(nxt)
            seq.append(nxt)
        assert out == ref
        assert out_spec == ref  # verify_step carries the sinks
