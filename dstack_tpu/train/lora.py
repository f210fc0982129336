"""LoRA fine-tuning on TPU.

The BASELINE target config is "Llama-3-8B LoRA on v5litepod-8"
(BASELINE.md). TPU-first design decisions:

- Adapters are *stacked per-layer factors* shaped like the base model's
  scanned weights, so they ride the same ``lax.scan`` — one fused layer
  body, no Python loop over layers (models/llama.py forward).
- The low-rank bypass is computed as ``s·(x·A)·B`` (two skinny matmuls)
  rather than materializing ``W + ΔW``: rank ≪ hidden keeps both
  matmuls MXU-friendly while avoiding a full-weight copy per step.
- Only adapters get optimizer state: base params are frozen inputs to
  the jitted step (donated separately), cutting optimizer HBM from
  2×params to 2×adapters — the reason LoRA fits a 8B model on v5e-8.

The reference (dstack) is an orchestrator and ships LoRA only as
examples (reference examples/fine-tuning/); here it is a first-class
training path exercised by the framework's own example configs.
"""

from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dstack_tpu.models import llama
from dstack_tpu.parallel.sharding import ShardingRules, default_rules, tree_shardings
from dstack_tpu.train.step import batch_sharding, chunked_cross_entropy

# logical out-axis of each adaptable projection (in-axis of A is the
# module's input axis); mirrors llama.param_specs
_MODULE_AXES: dict[str, tuple[Optional[str], Optional[str]]] = {
    "wq": ("embed_fsdp", "heads"),
    "wk": ("embed_fsdp", "kv_heads"),
    "wv": ("embed_fsdp", "kv_heads"),
    "wo": ("heads", "embed_fsdp"),
    "w_gate": ("embed_fsdp", "mlp"),
    "w_up": ("embed_fsdp", "mlp"),
    "w_down": ("mlp", "embed_fsdp"),
}


@dataclass(frozen=True)
class LoRAConfig:
    rank: int = 16
    alpha: float = 32.0
    target_modules: tuple = ("wq", "wk", "wv", "wo")

    @property
    def scale(self) -> float:
        return self.alpha / self.rank


def _module_dims(c: llama.LlamaConfig, name: str) -> tuple[int, int]:
    return {
        "wq": (c.hidden_size, c.q_dim),
        "wk": (c.hidden_size, c.kv_dim),
        "wv": (c.hidden_size, c.kv_dim),
        "wo": (c.q_dim, c.hidden_size),
        "w_gate": (c.hidden_size, c.intermediate_size),
        "w_up": (c.hidden_size, c.intermediate_size),
        "w_down": (c.intermediate_size, c.hidden_size),
    }[name]


def init_lora_params(
    config: llama.LlamaConfig, lora_config: LoRAConfig, key: jax.Array
) -> dict:
    """A ~ N(0, 1/r) and B = 0, so training starts at the base model."""
    if config.mla or config.first_k_dense:
        # MLA projections (wq_a/wq_b/wkv_a/wkv_b) and the DeepSeek
        # dense-prelude split don't map onto the wq/wk/wv adapter
        # naming or the uniform [n_layers, ...] stack — full fine-tune
        # covers these families (train/finetune.py --full)
        raise ValueError(
            "LoRA adapters are not supported for MLA/DeepSeek configs; "
            "use a full fine-tune (--full)"
        )
    L, r = config.n_layers, lora_config.rank
    layers: dict = {}
    keys = jax.random.split(key, len(lora_config.target_modules))
    for k, name in zip(keys, lora_config.target_modules):
        if name not in _MODULE_AXES:
            raise ValueError(f"unknown LoRA target module {name!r}")
        d_in, d_out = _module_dims(config, name)
        layers[f"{name}_lora_a"] = (
            jax.random.normal(k, (L, d_in, r), jnp.float32) / r
        ).astype(config.dtype)
        layers[f"{name}_lora_b"] = jnp.zeros((L, r, d_out), config.dtype)
    return {"layers": layers}


def lora_param_specs(lora_config: LoRAConfig) -> dict:
    """Logical-axis tree for the adapter pytree: shard the big dimension
    the same way its base module shards it; the rank dim is replicated."""
    layers: dict = {}
    for name in lora_config.target_modules:
        in_axis, out_axis = _MODULE_AXES[name]
        layers[f"{name}_lora_a"] = ("layers", in_axis, None)
        layers[f"{name}_lora_b"] = ("layers", None, out_axis)
    return {"layers": layers}


def merge_lora_params(
    params: dict, lora: dict, lora_config: LoRAConfig
) -> dict:
    """Fold adapters into the base weights (W ← W + s·A·B) for export /
    serving without the bypass cost."""
    merged_layers = dict(params["layers"])
    s = lora_config.scale
    for key, a in lora["layers"].items():
        if not key.endswith("_lora_a"):
            continue
        name = key[: -len("_lora_a")]
        b = lora["layers"][f"{name}_lora_b"]
        delta = jnp.einsum("lir,lro->lio", a.astype(jnp.float32), b.astype(jnp.float32)) * s
        merged_layers[name] = (
            merged_layers[name].astype(jnp.float32) + delta
        ).astype(params["layers"][name].dtype)
    return {**params, "layers": merged_layers}


def lora_state_specs(
    config: llama.LlamaConfig,
    lora_config: LoRAConfig,
    optimizer: optax.GradientTransformation,
    rules: ShardingRules,
    mesh: Mesh,
) -> tuple:
    """→ (base_params_sharding, lora_state_sharding)."""
    base_sh = tree_shardings(llama.param_specs(config), mesh, rules)
    lora_sh = tree_shardings(lora_param_specs(lora_config), mesh, rules)
    lora_abs = jax.eval_shape(
        lambda: init_lora_params(config, lora_config, jax.random.key(0))
    )
    opt_abs = jax.eval_shape(optimizer.init, lora_abs)
    repl = NamedSharding(mesh, P())
    # path-suffix matching (shapes collide: wq/wo adapters share a shape
    # whenever q_dim == hidden — see step.mirror_opt_shardings)
    from dstack_tpu.train.step import mirror_opt_shardings

    opt_sh = mirror_opt_shardings(lora_abs, lora_sh, opt_abs, repl)
    state_sh = {"lora": lora_sh, "opt_state": opt_sh, "step": repl}
    return base_sh, state_sh


def sharded_lora_init(
    config: llama.LlamaConfig,
    lora_config: LoRAConfig,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    rules: Optional[ShardingRules] = None,
    seed: int = 0,
    params: Optional[dict] = None,
) -> tuple[dict, dict, tuple]:
    """→ (base_params, lora_state, (base_sharding, state_sharding));
    everything initialized directly sharded (no host gather).

    ``params``: start from these base weights (host or device tree,
    e.g. an HF checkpoint) instead of random init."""
    rules = rules or default_rules()
    base_sh, state_sh = lora_state_specs(config, lora_config, optimizer, rules, mesh)

    key = jax.random.key(seed)
    if params is not None:
        params = jax.device_put(params, base_sh)
    else:
        params = jax.jit(
            lambda k: llama.init_params(config, k), out_shardings=base_sh
        )(key)

    def init_state(k):
        lora = init_lora_params(config, lora_config, k)
        return {
            "lora": lora,
            "opt_state": optimizer.init(lora),
            "step": jnp.zeros((), jnp.int32),
        }

    state = jax.jit(init_state, out_shardings=state_sh)(
        jax.random.fold_in(key, 1)
    )
    return params, state, (base_sh, state_sh)


def make_lora_train_step(
    config: llama.LlamaConfig,
    lora_config: LoRAConfig,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    rules: Optional[ShardingRules] = None,
    attn_impl: Optional[str] = None,
    grad_accum: int = 1,
) -> Callable:
    """Jitted (base_params, lora_state, batch) → (lora_state, metrics).

    Base params are a frozen input: no grads, no optimizer state, not
    donated (they are reused every step). ``grad_accum > 1`` scans that
    many microbatches (mask-weighted average) before one update —
    see train/step.py."""
    rules = rules or default_rules()
    base_sh, state_sh = lora_state_specs(config, lora_config, optimizer, rules, mesh)
    b_sh = batch_sharding(mesh, rules)
    batch_sh = {"tokens": b_sh, "targets": b_sh, "mask": b_sh}
    repl = NamedSharding(mesh, P())

    def loss_fn(lora, params, batch):
        hidden = llama.forward(
            params,
            batch["tokens"],
            config,
            mesh=mesh,
            rules=rules,
            attn_impl=attn_impl,
            lora=lora,
            lora_scale=lora_config.scale,
            return_hidden=True,
        )
        # the same logits as forward()'s tail, a sequence chunk at a
        # time: the naive log_softmax over [B, T, V] f32 logits is two
        # 7.8 GB tensors at the default batch 8 × seq 2048 on a 128k
        # vocab — more than the chip the adapters were meant to fit on
        # (the TPU compiler refused the step at 21.75 of 16 GB)
        head = (
            params["embed"].T if config.tie_embeddings else params["lm_head"]
        ).astype(config.dtype)
        loss, _ = chunked_cross_entropy(
            hidden, head, batch["targets"], batch.get("mask"),
            rules=rules, mesh=mesh, softcap=config.logit_softcap,
            logit_scale=config.logit_scale,
        )
        return loss

    def accum_grads(lora, params, batch):
        micro = jax.tree.map(
            lambda a: a.reshape(
                (grad_accum, a.shape[0] // grad_accum) + a.shape[1:]
            ),
            batch,
        )

        def body(carry, mb):
            g_acc, loss_acc, w_acc = carry
            loss, g = jax.value_and_grad(loss_fn)(lora, params, mb)
            w = jnp.maximum(mb["mask"].astype(jnp.float32).sum(), 1.0)
            g_acc = jax.tree.map(lambda a, b: a + b * w, g_acc, g)
            return (g_acc, loss_acc + loss * w, w_acc + w), None

        zeros = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), lora)
        (g, loss, w), _ = jax.lax.scan(
            body, (zeros, jnp.zeros(()), jnp.zeros(())), micro
        )
        grads = jax.tree.map(lambda a, l: (a / w).astype(l.dtype), g, lora)
        return loss / w, grads

    def step(params, state, batch):
        if grad_accum > 1:
            loss, grads = accum_grads(state["lora"], params, batch)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(state["lora"], params, batch)
        updates, opt_state = optimizer.update(grads, state["opt_state"], state["lora"])
        lora = optax.apply_updates(state["lora"], updates)
        new_state = {
            "lora": lora,
            "opt_state": opt_state,
            "step": state["step"] + 1,
        }
        return new_state, {"loss": loss, "grad_norm": optax.global_norm(grads)}

    return jax.jit(
        step,
        in_shardings=(base_sh, state_sh, batch_sh),
        out_shardings=(state_sh, {"loss": repl, "grad_norm": repl}),
        donate_argnums=(1,),
    )
