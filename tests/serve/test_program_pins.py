"""Device-free pins of the serving programs: the sha256 of each
program's lowered StableHLO text with abstract weights and cache, for
every configuration the benchmark runs, at its cell's shapes, and at
tiny sizes for every family branch of the shared layer code that no
cell's configuration takes. The text carries no source locations, so
moving or renaming code does not move it; an operation added to, taken
from or reordered in the program does. A PR that means to change what
these programs compute takes the pins again (``python
tests/serve/test_program_pins.py`` prints them) and says so; one that
moves code or adds an architecture beside them must leave them as they
are."""

import dataclasses
import hashlib
import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: the three decode programs: taken on the tree of PR 26 (before issue 27
#: moved a line) and untouched until PR 35, which is the proof that PR 29
#: left the decode programs alone. The four prefill programs: taken again on
#: the tree of PR 29, which moved their cache from the layer scan's
#: xs → ys into its carry on purpose (the sameness tests of
#: ``test_prefill_inplace.py`` passed first; PR 28, refused for a claim
#: and not for its code, had taken the same four digests). The three
#: decode programs again on the tree of PR 35, which changed what they
#: compute on purpose: the unmasked latent attention reads a layer's keys
#: in blocks up to the longest live context (``engine._attend_live``)
#: where it took the layer's whole slice. With them moved the tiny latent
#: family's ``decode_step`` and ``verify_step`` below: five digests, and
#: no other (longdoc takes the same two functions by their masked branch)
PINS = {
    "decode_step": "d8f0ac9df959581722b53a5567c04f2000532ead84f3102dbfe52e1b34cc412a",
    "decode_loop": "8eff7caafd6a28bfdfea8b81f69909748279439a6fe59f9a70ced0af4edfd288",
    "verify_step": "1f33600d8f44c247e2b06264b823ebb78aff9bdd0a518b7a0546810f733d5e77",
    "prefill_chunk_step@0": "288d0834c3cce37700920d780da73acb7143ca300bd0939f209fe17830e6226e",
    "prefill_chunk_step@256": "69a3129b1016f7d17ad1a984fce1a54d0253457d1fb07c92cfef418748ea8638",
    "prefill_packed_step@2": "ec960d7ac9100619ad9f9353a2603d8ed7b0379497310d36c539bad7ff5bedcd",
    "prefill_packed_step@4": "12148c36bc0cf3d7115f858f3c7990cb1a1d049d7f6a349e1b7b82afcc89d730",
}

NAMES = (
    "decode_step", "decode_loop", "verify_step", "prefill_chunk_step@0",
    "prefill_chunk_step@256", "prefill_packed_step@2", "prefill_packed_step@4",
)

#: the other two configurations the benchmark runs, at their own
#: ``serve_flags`` (slots, ``--max-seq``); the layer groups prefill through
#: the packed program alone (``engine._packed_only``). Taken with
#: ``dstack_tpu/`` as PR 29 left it, before PR 31 (and PR 30, refused,
#: whose digests these are) moved a line of the layer bodies: that they
#: stand is the proof that every cell's programs are the ones the
#: ledger's PR 29 lines measured
_GROUPS = (
    "decode_step", "decode_loop", "verify_step", "prefill_packed_step@1",
    "prefill_packed_step@2", "prefill_packed_step@4",
)
CELLS = {
    "minitron-4b": (16, 1536, NAMES),
    "dots3-note-prev-5l-ep8": (16, 8192, _GROUPS),
    # taken on the tree of PR 33, which added the configuration and the
    # dense family's walk over layer groups it runs through (two K/V
    # caches, a period a scan step); the 68 pins above and below stood
    "laguna-s-2.1-13l-ep8": (16, 8192, _GROUPS),
    # taken on the tree of PR 37, which added the configuration and the
    # latent family's body for a layer of two attention sublayers with
    # the expert branch across them (``engine._latent_layer``); the 78
    # pins above and below stood. An unmasked latent model: a lone row
    # prefills by the serial chunk, so the seven programs of ``NAMES``
    "longcat-flash-chat-4l-ep32": (16, 8192, NAMES),
    # taken on the tree of PR 42, which added the configuration, the
    # linear-attention mixer (``models/kda.py``) and the latent family's
    # walk by periods with a state and a convolution tail a slot beside
    # the latent rows; the 89 pins above and below stood. An unmasked
    # latent model: the seven programs of ``NAMES``; its verify step
    # takes the drafts a row holds (``draft_len``), as the engine calls it
    "ling-3.0-flash-vl-13l-ep8": (16, 8192, NAMES),
    # taken on the tree of PR 44, which added the configuration, the
    # gated short-convolution mixer (``models/shortconv.py``) and the
    # grouped-query walk's tail a slot beside K/V rows that the full
    # layers alone hold; the 100 pins above and below stood but three of
    # ``gqa-groups`` (below). A grouped-query model of layer groups
    # prefills by the packed program alone (``engine._packed_only``, with
    # or without a window since PR 44): the six programs of ``_GROUPS``
    "lfm2-24b-a2b-ep8": (16, 8192, _GROUPS),
    # taken on the tree of PR 48, which added the configuration, the
    # selective state-space mixer (``models/mamba.py``), differential
    # attention, the gmu and cross layers and the walk by folded segments
    # (``llama.layer_segments``); the 110 pins above and below stood. 32
    # slots; the six programs of ``_GROUPS``
    "phi-4-mini-flash-reasoning": (32, 8192, _GROUPS),
}
#: The three configurations that hold a SHARE of their experts (longdoc,
#: mixed, agent: 6 + 6 + 7 digests) and the tiny family ``gqa-groups``
#: (4) were taken again on the tree of PR 38, on purpose, and no other:
#: their cache gained the leaf ``moe_reads`` (experts read / held a
#: routed layer call), every program of theirs adds to it, and their
#: decode steps read the picked experts alone (``moe._picked_experts``:
#: the three expert stacks stay whole beside the layer scan,
#: ``engine._expert_rows``). ``PINS`` (reasoning: every expert held, 0.79
#: of them picked a call: the capacity form), ``CELL_PINS["minitron-4b"]``
#: and the other 52 tiny digests stand letter for letter: the proof that
#: splitting ``moe.router`` into ``select`` / ``_routing_aux`` and
#: ``moe_mlp`` into its two forms left the capacity form's text alone.
#: Three digests were taken again on the tree of PR 46, on purpose, and
#: no other of the 110: ``moe.reads_picked_experts`` chooses by the bytes
#: each form streams a call (``moe.TRIP_BYTES`` 5.1 MB a loop trip beside
#: its expert, read on the chip) where it held the expected share of
#: experts picked under a constant 0.6. ``lfm2-24b-a2b-ep8``'s
#: ``decode_step`` and ``decode_loop`` (0.644 of 8 experts of 18.9 MB
#: expected at 16 tokens: 168.7 µs a layer call against 213.3) now read
#: the picked experts alone, the change that PR was for; and
#: ``longcat-flash-chat-4l-ep32``'s ``verify_step``, which its issue did
#: not foresee: a 16 x 5 grid over the 768-wide top-12 router expects
#: 0.716 of the 16 held experts picked, and at 75.5 MB an expert the loop
#: streams fewer bytes (1,245 µs a layer call against 1,705 on the chip).
#: No cell's traffic reaches a verify step. Every other grid stays: 0.716
#: of thinking's 64 experts of 11.8 MB (the loop 11 % behind there),
#: 0.92-0.99 in longdoc, mixed and rag; reasoning's 0.793 of 64 at
#: 17.3 MB keeps the capacity form at decode (the loop 2.4 % behind)
CELL_PINS = {
    "dots3-note-prev-5l-ep8": {
        "decode_step": "e0a2a0ea02be32c46fbd6666b4fae69a96b65062217f90cbd1247b465b60cebf",
        "decode_loop": "a7a2cc17071519b020574604a70871cb2ed8d7fd9c4b71612b2e575bd2853f96",
        "verify_step": "b95c5bf49106c2d05a2d82687a39cbc18a3afa0b1e50ce0cc87829af0d3fcd70",
        "prefill_packed_step@1": "34e3b1ede4fe4ae4c882b3c264a83c7e4e05674e64c1870dea1f9663dbe4b9c5",
        "prefill_packed_step@2": "e9cce82eb619f26f03c145a8914828d12fe78ce5d52f895c497c8122a66412c8",
        "prefill_packed_step@4": "3607cce1c0f585e59512f98d748c6f88b37176180938e26883e7cb84ffd4c5a3",
    },
    "laguna-s-2.1-13l-ep8": {
        "decode_step": "c5a49737d7d601db1c4edae43b06141c5d769151c601c253f08293c8fe096a65",
        "decode_loop": "62559353bd132075b395585d046df82dcc2e2a50a8f6ced721e82ac11068e41f",
        "verify_step": "d8010745e5bb6dbae1d2168617d20af973521f81755e3ae4cedde78e717e44df",
        "prefill_packed_step@1": "884abb7265c46548c6fdb2dc22a0b3d72e99f8ebae42dfffb0f0e5885ba2387b",
        "prefill_packed_step@2": "f9767e2c49eba6ad041e95825f5e682ee86be9a21419e0a4fc060599d1b57f44",
        "prefill_packed_step@4": "1950017918e4255e7a08473a2cb2d8c55cdc63be9158638d0ac9120a7df14e36",
    },
    "longcat-flash-chat-4l-ep32": {
        "decode_step": "58eb77fe356a7e98456547a1d9da51c7cedf84e13c52011984338c4d08766741",
        "decode_loop": "5d6c1e1796dbf5eb78f6a238c6d5a1c8508920f2f3268709795a3f6a7db7c3cf",
        "verify_step": "cc18ba84f37d31c0d2db54aeff49bd83fef9018f00c85d558ca87b11f7ef51b8",
        "prefill_chunk_step@0": "899a7a025997ee5b0f319e0b6208dc4bc1b422b3e18caf5525c53c68ae956345",
        "prefill_chunk_step@256": "d7b69b55ff596b5ca140e6bd3a77faeb7562f516b4528e53ba223ed1f6919afd",
        "prefill_packed_step@2": "bc4328d7ebb9e49e8e35b52632652771c38aba5f5c0aec1f9bd8e9028ca75e89",
        "prefill_packed_step@4": "1ed3346f1ccd9a788784dcb497e7899e9205362ff3e8cee967545b94345003d7",
    },
    "ling-3.0-flash-vl-13l-ep8": {
        "decode_step": "462679763f48dff2390b5168533b5e2369b2710a7cf20bd6d8bf501c87a75931",
        "decode_loop": "e53d89a286b66903b52620f80c0e22bc2cdc30627df608fcf237b7edf10c1b73",
        "verify_step": "f7ddd6d6ca2aff38c17898b3989dcc09eae9491eb8a7622db0a3756e11de6590",
        "prefill_chunk_step@0": "e1a732ad57bc2f5f5d12c48d37d7f540098210b2eb56a9a6d4d9bf7b2e0097a5",
        "prefill_chunk_step@256": "80d97c4da1c3e1be6813c67f8598b5f08594e50cfd016c81fb2237403e458026",
        "prefill_packed_step@2": "eeff4be09d59197cf36c431f66e9bfb59fafd4334f80040bfe3e318dec76f6ce",
        "prefill_packed_step@4": "58669b78efa6c435462dc49bbb024e5b374ad5ea176776db733f768b39cb4d5a",
    },
    "lfm2-24b-a2b-ep8": {
        "decode_step": "b079f16e34d0b2f2406cddafda048f14b262b1e2ed3c2eb07b9ab8e0c92f5445",
        "decode_loop": "271432d2b38d3659ca99782dc44b78f54eeca0d2f28b79ab4461cdcd0867edc5",
        "verify_step": "831769435377710277fbe4b0f23043ff4d1f2060c38c2d2bda9a9286ee306a5a",
        "prefill_packed_step@1": "3dd39d9e6936c07c7ec7b3948341a70250fbe7220133261eef9a9d66e093e87a",
        "prefill_packed_step@2": "f0294c4a5685eb1ca4a64a50fc6cb3a304a98dc89fb7e9cfe10160bddb6d4a6a",
        "prefill_packed_step@4": "21b94636920f6d61685f53211baf4fa765e0d544786fcbd440c97c6590fc89ee",
    },
    "phi-4-mini-flash-reasoning": {
        "decode_step": "bd3b547d2c475b278761a7b82eaa41be141eb1c1a449e3b0562a01c476274566",
        "decode_loop": "19ef8c8afdac26610100b80d2bdfd251f4140e462c48a455a2ffb09266d9111d",
        "verify_step": "ca0e75beb685e3bcf84cb688492f9de5d7773465fdf98c19d8dcfe12d29ce364",
        "prefill_packed_step@1": "87159cbaa7453ffee7edc7bcefa9aec98d0d4b18469d8436b59022720a39d603",
        "prefill_packed_step@2": "81f3d772bf1498c95d351f533a43471ac1fbdb036657b55fbfae655fda6ee3ab",
        "prefill_packed_step@4": "d8cdad464e245e6df71db816e4cbeee5abe0cb8cd5792674a51eadc36299db0f",
    },
    "minitron-4b": {
        "decode_step": "31cd7802fdfa5729183b1aa6346316af5f0a7b1d5a845041033888da8aa84ab7",
        "decode_loop": "e34435488f694a504f469e0c3efd623055e1bcdf4d454f75e7e485abcb946daa",
        "verify_step": "3f4716996fc4c65633393aa0a70ca37e2173a806635832a2dea31a955563ee46",
        "prefill_chunk_step@0": "1fd214445d110d29a491a4e92661d25bbbf1cf7f72c9d7fd84a2cee39ee54398",
        "prefill_chunk_step@256": "c671c1ca9a5dbf04362cefaf9947fe1082ba7012bcd7ddb942a8df154faf5168",
        "prefill_packed_step@2": "b83a35d4e5fbf5528b4132e788ef5822167fcf8c955ac7cc3fa70c93ce42750e",
        "prefill_packed_step@4": "0fc395260fd0b68897543d0d5fd909280b94973fb45875ed682024f805ab0624",
    },
}

#: one tiny configuration a family: ``llama.LLAMA_TINY`` with the fields a
#: published preset sets together, so that every branch of the layer code
#: the programs share is held where no cell's configuration takes it.
#: Three layers where a pattern must leave a tail (``grouped_scan_layout``)
_T = dict(n_layers=3)
FAMILIES = {
    # Qwen3: per-head q/k norm, q/k/v biases (Qwen2)
    "qwen": dict(qk_norm=True, qkv_bias=True, norm_eps=1e-6),
    # Llama4: NoPE layers with a query temperature, interleaved rope, the
    # weightless norm after it, attention within chunks
    "llama4": dict(
        n_layers=4, rope_interleaved=True, nope_pattern=2, qk_l2_norm=True,
        attention_chunk_size=16, attn_temp_scale=0.1, attn_temp_floor=8.0,
    ),
    # Gemma2: sandwich norms, score and logit soft-caps, alternating window
    "gemma2": dict(
        **_T, post_norms=True, attn_softcap=50.0, logit_softcap=30.0,
        sliding_window=8, sliding_pattern=2, norm_offset=True, embed_scale=True,
        hidden_act="gelu_tanh", tie_embeddings=True, attn_scale=24.0**-0.5,
    ),
    # Gemma3: dual rope over a window pattern, linear scaling, q/k norm
    "gemma3": dict(
        **_T, post_norms=True, qk_norm=True, sliding_window=8,
        sliding_pattern=2, rope_local_theta=10000.0,
        rope_scaling=("linear", 8.0), norm_offset=True, embed_scale=True,
        hidden_act="gelu_tanh", tie_embeddings=True,
    ),
    # StarCoder2: biases on every projection, gateless MLP, LayerNorm + bias
    "starcoder2": dict(
        proj_bias=True, qkv_bias=True, mlp_gateless=True,
        norm_type="layernorm_bias", hidden_act="gelu_tanh", sliding_window=8,
        tie_embeddings=True,
    ),
    # Granite: scalar multipliers on embeddings, sublayer outputs, logits
    "granite": dict(
        residual_multiplier=0.22, embed_multiplier=12.0, logit_scale=0.125,
        attn_scale=0.015625,
    ),
    # Command-R: parallel block under one LayerNorm, interleaved rope
    "cohere": dict(
        parallel_block=True, norm_type="layernorm", rope_interleaved=True,
        logit_scale=0.0625, tie_embeddings=True,
    ),
    # OLMo-2: no pre-norms, sublayer outputs normed, q/k normed at full width
    "olmo2": dict(pre_norm=False, post_norms=True, qk_norm_flat=True),
    # GLM-4: partial interleaved rotary, sandwich norms, q/k/v biases
    "glm": dict(
        partial_rotary=0.5, rope_interleaved=True, qkv_bias=True,
        post_norms=True,
    ),
    # gpt-oss: attention sinks over an alternating window, biased experts
    "gpt-oss": dict(
        attn_sinks=True, qkv_bias=True, proj_bias=True, sliding_window=8,
        sliding_pattern=2, n_experts=4, experts_per_token=2,
        capacity_factor=2.0, router_topk_softmax=True, moe_bias=True,
        moe_act="oai_glu",
        rope_scaling=("yarn", 32.0, 32.0, 1.0, 64.0, 1.3465735902799727, False),
    ),
    # grouped-query layer GROUPS (PR 33): window layers of another query
    # head count in rings, yarn on half a head beside a plain local rope,
    # per-head gates, a dense first layer, half of the experts held
    "gqa-groups": dict(
        n_layers=6, layer_types=("full", "window", "window", "full", "window", "window"),
        sliding_window=8, swa_n_heads=6, attn_gate=True, partial_rotary=0.5,
        swa_partial_rotary=1.0, rope_local_theta=10000.0,
        rope_scaling=("yarn", 8.0, 32.0, 1.0, 16.0, 1.2), n_experts=4,
        experts_per_token=2, experts_held=(2, 2), capacity_factor=2.0,
        router_score="sigmoid", router_renorm=True, routed_scale=2.5,
        moe_shared_expert=True, first_k_dense=1,
    ),
}
#: ``scmoe-tiny`` (PR 37): a latent layer of two attention sublayers and
#: two dense FFNs, the expert branch across them, identity experts among
#: the router's outputs, every real expert held (no counts in the cache)
#: ``linear-tiny`` (PR 42): linear-attention layers beside latent ones in
#: two periods behind a linear dense prelude, group-limited routing with
#: one group of four held
#: ``conv-tiny`` (PR 44): gated short-convolution layers beside
#: grouped-query ones in two periods behind a conv dense prelude, q/k
#: norms, a tied head, half of the experts held. With it ``gqa-groups``'
#: ``decode_step``, ``verify_step`` and ``prefill_chunk_step@16`` were
#: taken again on purpose, and no other: at a head_dim that leaves its
#: K/V leaves with their tokens on the lanes (32 there, 64 in the rag
#: cell) a model of layer groups writes a step's rows unrolled and a
#: lone row's chunk as one ``dynamic_update_slice`` (the mixed cell's
#: head_dim is 128: its six pins stand)
#: ``ssm-tiny`` (PR 48): mamba, differential window | full, gmu and cross
#: layers in two folded segments, LayerNorms with biases, no rotary
TINY = tuple(sorted(FAMILIES)) + (
    "mla-tiny", "moe-tiny", "scmoe-tiny", "linear-tiny", "conv-tiny",
    "ssm-tiny",
)
TINY_NAMES = (
    "decode_step", "verify_step", "prefill_chunk_step@16", "prefill_packed_step@2",
)
TINY_B, TINY_T, TINY_CHUNK, TINY_S = 4, 64, 16, 3
TINY_PINS = {
    "cohere": {
        "decode_step": "576ecb5c1b7bee715c913121a4e2a8b7c1a41f9ae21594264337b3adf15b3dbc",
        "verify_step": "bb44b8c41168b7e0dba1f6073eddb29913a262d94844f5f3099761ef9b761853",
        "prefill_chunk_step@16": "ca6baaf4143912fbe1d8374e68c78f294deb7b9b31f2e664baecaa017edde208",
        "prefill_packed_step@2": "21e1a51a0d9401fc10951d20c063f7c04eb2c7dc1a99d1eead043bf0f81fdb14",
    },
    "gemma2": {
        "decode_step": "2c57b109ed29fa1f5da9cf1fd32d1e6f12253b659eac662d722c648b8de426c0",
        "verify_step": "9021d036710eb8b5174a909f9b4aa4a51de32e94c2e5df5199b9fc04dc162a32",
        "prefill_chunk_step@16": "e197874c2865547eea15e6678f5f837b58d264718cdc5b601db48efb2f43959b",
        "prefill_packed_step@2": "c84582ff0dd6912f8ea0428a53b3df97eacfebd8299c0a38b66c9b9a0d3fd7e7",
    },
    "gemma3": {
        "decode_step": "7b615e48f951a19cf2390724fd67aee58961e189b9ee46d528b0c36658f60764",
        "verify_step": "8a027eb8056c752abc81d06d0203139331f12c50c0a8bc77fb62345728879d9b",
        "prefill_chunk_step@16": "1079ef7b360c46d5249094f5f31c8e3b9826c82e016775cdab8021d569893ce2",
        "prefill_packed_step@2": "ee8dec3ed2330a18718e3091edd49b1e7e7b800df5a8347d2b3f85a9ba42933f",
    },
    "glm": {
        "decode_step": "556b447d01973fccf1ba709bcfd7b7223774a1a33a05cf39b8a96b1b50935fe6",
        "verify_step": "ac4bd96b997db340015b72afdc1fae9dda8d9d063904402fbe6b18a509d944ea",
        "prefill_chunk_step@16": "5f0688f6f7f2faa31d0098d54d76499aa7638252008716bc1830734fc50098d6",
        "prefill_packed_step@2": "a2b707978c3fed0da43125fe871fa915d886004d1bd974c19f27e1b7bb107f61",
    },
    "gpt-oss": {
        "decode_step": "e6e3f7ead64ceebe6147394948ce9c5c0e081f7ca78991f0421cf311c575a3b0",
        "verify_step": "fa6b990ea15fe784448a9b0326357552c4d9936421d42bc2e96a5d1f26bd4f54",
        "prefill_chunk_step@16": "2c7ba836228990048a6ed63710708de8de8b8f530512278d2493d07937d8a666",
        "prefill_packed_step@2": "82f7f8f13adb534e885927804ccb4a0616258e897d2a50f8afa4aa0d5a852ca5",
    },
    "granite": {
        "decode_step": "f963b8df23d3968543f04e2d0445295af7a6f14a9cefe08955a1e582d2db7399",
        "verify_step": "580a18428995baa699afed18fbc19126cb7d90b3b78daceb03d0e4abb57006e4",
        "prefill_chunk_step@16": "f44375ce07ba6844da767efa3f4b4c9a0a73d01877276f2fa6cddf63bbc3ac49",
        "prefill_packed_step@2": "f7bcfb5ec694a95858b83bc7141d62b3495698a3a4d3dd5f23637e8021e984c4",
    },
    "llama4": {
        "decode_step": "ddf5ebc0e765eaba494cdd6738873ba42a490404af125252a430bf693ceeaeea",
        "verify_step": "9ee55da6e0ab403cfee9ea7ad17de57b09739019ca0f1dfecb185c1ce83d8643",
        "prefill_chunk_step@16": "dfed07d9eb01cb35da0d0e59e7ab420b25f3b5cb22c91c8f97bf89f20ff7370f",
        "prefill_packed_step@2": "0fca725d6eaa88943bce37405b563b485dd898a8e68683bbbfbbf86749b7caf0",
    },
    "olmo2": {
        "decode_step": "355e24e284b84c77dc6dc70000ec5b596daac5dd0c591867e58c14af0a837f04",
        "verify_step": "1139c9c94a0f52782b18c3bd77f9f2c6934e03d6fb8310ff3e80e68453d3cfec",
        "prefill_chunk_step@16": "fdc038c351a20f2d502f979eae86ffb58115034750a7681c041a51bff2a0afb1",
        "prefill_packed_step@2": "e93acd9839786142f28538515d4aee63420e7cb0f075f51039f1589bb19b086d",
    },
    "qwen": {
        "decode_step": "c6dea9d703e0bcd932ae528b9cf6977fb3a159dacb5608def7badcf3ad6b952a",
        "verify_step": "599ae25aa793679a3730f89c988ab810ca801409d415d4759f116e993681e061",
        "prefill_chunk_step@16": "74903d7386f088ee07b6165d3db6418d9d3e43b8d532d1b2eb712a34e243440f",
        "prefill_packed_step@2": "c79d4bfe3331501c02f297a304c7df82e4a2db227662adfa40ff442957582817",
    },
    "starcoder2": {
        "decode_step": "13c009d35cffca17cbf35882e78a38af918e6cca3c4d2bc272d1cf24ebef3b2b",
        "verify_step": "c0fd3d53be170bc18cf0ef868145eb59f1f621a88a7c98eb0b28d51892656d2e",
        "prefill_chunk_step@16": "3c95c42101623311b2bdfe7e627984666160fec5fdef1df42928cb741aa1e935",
        "prefill_packed_step@2": "7b42bbab4e82ee37d5d454b1bdfae798046ee7d0a48557ff405556a4dbf5eec6",
    },
    "gqa-groups": {
        "decode_step": "446225fb2a83b956e11b7fb1c25a8e7f24c79f0bf38708540b7572c7802f7f50",
        "verify_step": "0112d66685934c9ee871d89f1bba439975dc79f11eaa0fff3da8ae45654fd03c",
        "prefill_chunk_step@16": "e5977842577cac93e7d58dcb42946111b5943d96a295aaec7ef28d45d02f65af",
        "prefill_packed_step@2": "a72fe2af95da0bc050c70b77d610a4f5321fb91dd07ddd87716f12849996568e",
    },
    "mla-tiny": {
        "decode_step": "27af4d94e53f4047e59ffdf160d397516a57df2c6c48bc8e0501d2fad3cff96c",
        "verify_step": "f70fa58eed2dbfc39b01c77c71a26d8048ad40a49a204f1c5ba9e4c93d3c39c5",
        "prefill_chunk_step@16": "39a09faca0a1cd213c1cd45111cc1df775fa8184ae6a6f987b3dd53f0fe3939f",
        "prefill_packed_step@2": "600d47229f1fce5a68d0d4719b94780c6e6c152aeb80022daa3084af7b680c9d",
    },
    "scmoe-tiny": {
        "decode_step": "d2e810aa2f7cb1be4848b93830d341d9e21395d3292de5a836af2de2279f2a14",
        "verify_step": "22caee88aef102fc7884f239b90ff19e4deb987c3218cf1a4b060a1776631905",
        "prefill_chunk_step@16": "8297db34a0663aaa48b4ab0c26a0716f947a6ac0aa34c8e0dae6685ce5a035a7",
        "prefill_packed_step@2": "70e7b0ca3f571e66510cce28dc91707c976da446fbe0139e4b0e435aba622c75",
    },
    "linear-tiny": {
        "decode_step": "e9850ca57d5c2dedc0d26233a99c1962f601e52810e03285afd45230a9005abc",
        "verify_step": "9650fb4357b2d327717113cfd2594484ddc11960d52d823b986a02db270d5381",
        "prefill_chunk_step@16": "d7dadee9a22dedb94a4c1a69d0188752e69709d79cb1ff87de560838958f126d",
        "prefill_packed_step@2": "7d8692bad7b323f39e503c39473bfcc467490c828584bcc409672ddfa3a77e83",
    },
    "conv-tiny": {
        "decode_step": "0b0e57c1041e611e243990cb86f81009e80cef5c27cbaee14d87cb0e1e03d571",
        "verify_step": "5b9d5ac4f0b6f2529426c9532064d623f78b66b75571b7801d488cf9cda870c1",
        "prefill_chunk_step@16": "96fdde80fbe29401460aa89cf3bb15981600b8f6809e4875e8a6d069d6ff2d83",
        "prefill_packed_step@2": "3c17d38e5daf5b3d4302665a6ab3d4e474f8214d8c8632e4f728c16e2684bedc",
    },
    "ssm-tiny": {
        "decode_step": "57bea30bd013eab27d8b590d24fbecb7f9a30bbb1b2c3517da36fcf6efd25deb",
        "verify_step": "95292efc7cdda9d6e87965bfd32444577860b5de8f6df464add4ede3ddb2dd63",
        "prefill_chunk_step@16": "d464c2945f9a357925548b0bb3e550419ba6ada3525d30576d4c0643c429ee8c",
        "prefill_packed_step@2": "340999a61ec15fad3aa9517de2f0b687f2ab345bff23cf5ecd0fea7a99376619",
    },
    "moe-tiny": {
        "decode_step": "a38072b4a98bfd0c163d6cec124ee7e5712705874e59bee3e100c69fdb1838bf",
        "verify_step": "4a1635c4f19ad8db74c2ee9a026759ac91302635b86006703c8e2222d991f4d4",
        "prefill_chunk_step@16": "5b68e440c943f9a5dfad45aa7d5d406047b697b9e7cdc273620b708ed907fae2",
        "prefill_packed_step@2": "3bade8438bfd14ddf4f22c7db5798ac2f0573d1e863f23228968cdc33edcb442",
    },
}


def _cell_config(config: str):
    from benchmark import launch

    with open(os.path.join(ROOT, "benchmark", "configs", config + ".json")) as f:
        cfg = json.load(f)
    return launch.build_llama_config(cfg["llama_config"])


def _tiny_config(family: str):
    from dstack_tpu.models import llama

    if family in FAMILIES:
        return dataclasses.replace(llama.LLAMA_TINY, **FAMILIES[family])
    return llama.CONFIGS[family]


def _lower(c, name: str, B: int, T: int, chunk: int = 256, S: int = 5):
    """Program ``name`` of configuration ``c`` lowered for ``B`` slots of
    ``T`` cache rows (``chunk`` tokens a prefill row, ``S`` a verify row)."""
    from dstack_tpu.models import llama
    from dstack_tpu.serve import engine as E

    params = llama.abstract_params(c)
    cache = jax.eval_shape(lambda: E.init_cache(c, B, T, chunk=chunk))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    flag = jax.ShapeDtypeStruct((B,), jnp.bool_)
    if name == "decode_step":
        fn = partial(E.decode_step, config=c)
        args = (params, cache, i32(B), i32(B))
        kw = {"write_mask": flag}
    elif name == "decode_loop":
        fn = partial(E.decode_loop, config=c, steps=8, max_seq=T)
        args = (params, cache, i32(B), i32(B), i32(B), flag, i32(B))
        kw = {}
    elif name == "verify_step":
        fn = partial(E.verify_step, config=c)
        args = (params, cache, i32(B, S), i32(B))
        kw = {"write_mask": flag}
        if {"linear", "conv"} & set(c.layer_types):  # the states advance by the drafts that stand
            kw["draft_len"] = i32(B)
    elif name.startswith("prefill_chunk_step"):
        fn = partial(E.prefill_chunk_step, config=c, start=int(name.split("@")[1]))
        args = (params, cache, i32(1, chunk), i32(), i32())
        kw = {}
    elif name.startswith("prefill_packed_step"):
        g = int(name.split("@")[1])
        fn = partial(E.prefill_packed_step, config=c)
        args = (params, cache, i32(g, chunk), i32(g), i32(g), i32(g))
        kw = {}
    else:
        raise KeyError(name)
    return jax.jit(fn).lower(*args, **kw)


def _lowered(name: str):
    return _lower(_cell_config("deepseek-v2-lite-9l"), name, 16, 8192)


def _sha(lowered) -> str:
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()


def digest(name: str) -> str:
    return _sha(_lowered(name))


def cell_digest(config: str, name: str) -> str:
    B, T, _ = CELLS[config]
    return _sha(_lower(_cell_config(config), name, B, T))


def tiny_digest(family: str, name: str) -> str:
    return _sha(_lower(
        _tiny_config(family), name, TINY_B, TINY_T, chunk=TINY_CHUNK, S=TINY_S
    ))


@pytest.mark.parametrize("name", NAMES)
def test_latent_programs_lower_to_what_they_did(name):
    assert digest(name) == PINS[name]


@pytest.mark.parametrize(
    "config,name", [(c, n) for c in sorted(CELLS) for n in CELLS[c][2]]
)
def test_cell_programs_lower_to_what_they_did(config, name):
    assert cell_digest(config, name) == CELL_PINS[config][name]


@pytest.mark.parametrize("family,name", [(f, n) for f in TINY for n in TINY_NAMES])
def test_family_programs_lower_to_what_they_did(family, name):
    assert tiny_digest(family, name) == TINY_PINS[family][name]


if __name__ == "__main__":
    import sys

    sys.path.insert(0, ROOT)
    print("PINS =", json.dumps({n: digest(n) for n in NAMES}, indent=4))
    print("CELL_PINS =", json.dumps(
        {c: {n: cell_digest(c, n) for n in CELLS[c][2]} for c in sorted(CELLS)},
        indent=4,
    ))
    print("TINY_PINS =", json.dumps(
        {f: {n: tiny_digest(f, n) for n in TINY_NAMES} for f in TINY}, indent=4
    ))
