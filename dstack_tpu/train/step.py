"""Training step: sharded init, loss, optimizer update.

The full train step is one ``jit`` over the mesh: forward (bf16, remat),
backward, optax update — XLA inserts all collectives (reduce-scatter/
all-gather for fsdp, psum for tp) from the shardings alone.
"""

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dstack_tpu.models import llama
from dstack_tpu.parallel.sharding import (
    ShardingRules,
    constrain,
    default_rules,
    tree_shardings,
)


def cross_entropy_loss(
    logits: jax.Array,  # [B, T, V] f32
    targets: jax.Array,  # [B, T] int32
    mask: Optional[jax.Array] = None,  # [B, T] 0/1
) -> tuple[jax.Array, jax.Array]:
    """Returns (mean loss, total weight)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is None:
        mask = jnp.ones_like(targets, jnp.float32)
    mask = mask.astype(jnp.float32)
    total = jnp.maximum(mask.sum(), 1.0)
    return -(ll * mask).sum() / total, total


def fused_cross_entropy(
    x: jax.Array,  # [B, T, H] final hidden (model dtype)
    head: jax.Array,  # [H, V]
    targets: jax.Array,  # [B, T] int32
    mask: Optional[jax.Array],  # [B, T] 0/1
    rules: Optional[ShardingRules] = None,
    mesh: Optional[Mesh] = None,
    softcap: float = 0.0,  # Gemma2 final-logit tanh cap
) -> tuple[jax.Array, jax.Array]:
    """Cross-entropy in logsumexp form: loss = lse(logits) − logit[y].

    Never materializes a full-vocab f32 log-*probability* tensor (a
    second ~4 GB allocation in the naive log_softmax+gather form): only
    the f32-accumulated logits exist, consumed by logsumexp/gather
    reductions whose outputs are [B, T]. On tensor-parallel meshes the
    logits are constrained over the vocab axis (pass rules+mesh).
    """
    logits = jnp.einsum(
        "bth,hv->btv", x, head, preferred_element_type=jnp.float32
    )
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    if rules is not None:
        logits = constrain(logits, rules, "batch", "seq", "vocab", mesh=mesh)
    lse = jax.nn.logsumexp(logits, axis=-1)  # [B, T]
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    if mask is None:
        mask = jnp.ones_like(targets, jnp.float32)
    mask = mask.astype(jnp.float32)
    total = jnp.maximum(mask.sum(), 1.0)
    return ((lse - tgt) * mask).sum() / total, total


def chunked_cross_entropy(
    x: jax.Array,  # [B, T, H] final hidden (model dtype)
    head: jax.Array,  # [H, V]
    targets: jax.Array,  # [B, T] int32
    mask: Optional[jax.Array],  # [B, T] 0/1
    max_chunk_bytes: int = 256 * 1024 * 1024,
    rules: Optional[ShardingRules] = None,
    mesh: Optional[Mesh] = None,
    softcap: float = 0.0,  # Gemma2 final-logit tanh cap
    logit_scale: float = 0.0,  # Cohere logit multiplier (0 = off)
) -> tuple[jax.Array, jax.Array]:
    """LM-head matmul fused into the loss, chunked over the sequence.

    Full-vocab f32 logits for a Llama vocab are ~4 GB at [8, 1024, 128k]
    — the single largest HBM allocation of a train step. Scanning the
    head+softmax over sequence chunks (with remat on the chunk body so
    the backward recomputes chunk logits) keeps peak HBM at one chunk of
    logits while the MXU still sees large [B·Tc, H]×[H, V] matmuls.
    """
    b, t, h = x.shape
    v = head.shape[-1]
    if mask is None:
        mask = jnp.ones_like(targets, jnp.float32)
    mask = mask.astype(jnp.float32)
    # pick the largest chunk count (dividing T) that fits the budget
    chunk_bytes = lambda c: b * (t // c) * v * 4
    c = 1
    while c < t and (chunk_bytes(c) > max_chunk_bytes or t % c != 0):
        c += 1
    while t % c != 0:
        c += 1
    tc = t // c

    xs = jnp.moveaxis(x.reshape(b, c, tc, h), 1, 0)  # [C, B, Tc, H]
    ts = jnp.moveaxis(targets.reshape(b, c, tc), 1, 0)
    ms = jnp.moveaxis(mask.reshape(b, c, tc), 1, 0)

    def chunk(carry, xtm):
        xc, tcg, mc = xtm
        logits = jnp.einsum(
            "bth,hv->btv", xc, head, preferred_element_type=jnp.float32
        )
        if logit_scale:
            logits = logits * logit_scale
        if softcap:
            logits = softcap * jnp.tanh(logits / softcap)
        if rules is not None:
            logits = constrain(logits, rules, "batch", "seq", "vocab", mesh=mesh)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, tcg[..., None], axis=-1)[..., 0]
        nll, w = carry
        return (nll - (ll * mc).sum(), w + mc.sum()), None

    (nll, w), _ = jax.lax.scan(
        jax.checkpoint(chunk), (jnp.zeros(()), jnp.zeros(())), (xs, ts, ms)
    )
    total = jnp.maximum(w, 1.0)
    return nll / total, total


def rules_for_mesh(mesh: Mesh, rules: Optional[ShardingRules] = None) -> ShardingRules:
    """Default sharding rules for a mesh: on pipeline meshes (pp > 1) the
    stacked ``layers`` dim is sharded over ``pp`` so each stage's weights
    and optimizer state live only on their stage's devices."""
    if rules is not None:
        return rules
    if mesh.shape.get("pp", 1) > 1:
        return default_rules({"layers": "pp"})
    return default_rules()


def default_optimizer(
    lr: float = 3e-4,
    weight_decay: float = 0.1,
    warmup: int = 100,
    decay_steps: int = 10000,
    opt_bits: int = 32,
) -> optax.GradientTransformation:
    """``opt_bits=8`` stores the Adam moments as blockwise int8
    (train/opt8.py) — ~4x less optimizer HBM state and traffic; the
    update math itself stays f32."""
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, lr, warmup_steps=warmup, decay_steps=max(decay_steps, warmup + 1)
    )
    if opt_bits == 8:
        from dstack_tpu.train.opt8 import adamw8

        return optax.chain(
            optax.clip_by_global_norm(1.0),
            adamw8(schedule, b1=0.9, b2=0.95, weight_decay=weight_decay),
        )
    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(schedule, b1=0.9, b2=0.95, weight_decay=weight_decay),
    )


def mirror_opt_shardings(params_abs, param_sh, opt_abs, repl) -> Any:
    """Shardings for an optax state tree: optax states embed copies of
    the param tree (ScaleByAdamState.mu/nu, …), so each opt leaf whose
    tree path *ends with* a param path inherits that param's sharding.

    Path-suffix matching, NOT shape matching — distinct params can share
    a shape with different shardings (wq [L,h,h] vs wo [L,h,h] when
    q_dim == hidden, as in every Llama config).

    Opt leaves that share a param's path but not its shape (the int8
    optimizer's per-block scale tensors, shaped param.shape[:-1] +
    (nblocks,)) inherit the param's sharding with the LAST axis
    replicated — leading axes still shard with the moment codes they
    scale, so dequant needs no communication."""
    param_paths = {
        tuple(str(k) for k in path): (sh, leaf.shape)
        for (path, leaf), sh in zip(
            jax.tree_util.tree_leaves_with_path(params_abs),
            jax.tree.leaves(param_sh),
        )
    }

    def leaf_sh(path, leaf):
        p = tuple(str(k) for k in path)
        for i in range(len(p)):
            if p[i:] in param_paths:
                sh, pshape = param_paths[p[i:]]
                if leaf.shape == pshape:
                    return sh
                if (
                    len(leaf.shape) == len(pshape)
                    and leaf.shape[:-1] == pshape[:-1]
                    and isinstance(sh, NamedSharding)
                ):
                    spec = list(sh.spec) + [None] * (
                        len(pshape) - len(sh.spec)
                    )
                    return NamedSharding(sh.mesh, P(*spec[:-1], None))
                return repl
        return repl

    return jax.tree_util.tree_map_with_path(leaf_sh, opt_abs)


def state_specs(config: llama.LlamaConfig, optimizer: optax.GradientTransformation, rules: ShardingRules, mesh: Mesh) -> dict:
    """Shardings for the full train state (params + opt state + step)."""
    pspecs = llama.param_specs(config)
    param_sh = tree_shardings(pspecs, mesh, rules)
    params_abs = llama.abstract_params(config)
    opt_abs = jax.eval_shape(optimizer.init, params_abs)
    repl = NamedSharding(mesh, P())
    opt_sh = mirror_opt_shardings(params_abs, param_sh, opt_abs, repl)
    return {"params": param_sh, "opt_state": opt_sh, "step": repl}


def batch_sharding(mesh: Mesh, rules: ShardingRules) -> NamedSharding:
    return rules.mesh_sharding(mesh, ("batch", "seq"))


def sharded_init(
    config: llama.LlamaConfig,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    rules: Optional[ShardingRules] = None,
    seed: int = 0,
    params: Optional[dict] = None,
) -> tuple[dict, dict]:
    """Initialize the train state directly sharded (no host gather).

    ``params``: start from these weights (host or device tree, e.g. an
    HF checkpoint) instead of random init — they go straight into the
    sharded buffers and only opt_state/step are built on device, so
    peak memory stays at one parameter tree.

    Returns (state, state_shardings).
    """
    rules = rules_for_mesh(mesh, rules)
    shardings = state_specs(config, optimizer, rules, mesh)

    if params is not None:
        params = jax.device_put(params, shardings["params"])
        state = {
            "params": params,
            "opt_state": jax.jit(
                optimizer.init, out_shardings=shardings["opt_state"]
            )(params),
            "step": jax.device_put(jnp.zeros((), jnp.int32), shardings["step"]),
        }
        return state, shardings

    def init(key):
        params = llama.init_params(config, key)
        return {
            "params": params,
            "opt_state": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32),
        }

    key = jax.random.key(seed)
    state = jax.jit(init, out_shardings=shardings)(key)
    return state, shardings


def make_train_step(
    config: llama.LlamaConfig,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    rules: Optional[ShardingRules] = None,
    attn_impl: Optional[str] = None,
    loss_impl: str = "fused",  # "fused" | "chunked"
    n_micro: Optional[int] = None,
    grad_accum: int = 1,
) -> Callable:
    """Build the jitted train step: (state, batch{tokens,targets,mask}) →
    (state, metrics).

    ``loss_impl`` picks the LM-head/loss fusion: "fused" (one f32-
    accumulated logits tensor, reductions fused — fastest) or "chunked"
    (sequence-chunked scan with remat — lowest peak HBM, for memory-
    tight configs).

    ``grad_accum > 1`` splits the batch's leading dim into that many
    microbatches and scans them, averaging gradients before ONE
    optimizer update — the effective batch scales past what activations
    fit in HBM, at one extra params-sized f32 accumulator. Masked token
    counts weight the average, so ragged masks stay exact.

    On pipeline meshes (pp > 1) the layer stack runs through
    ``forward_pipelined`` with ``n_micro`` microbatches (default: pp).
    MoE configs (n_experts > 0) add the router aux losses to the
    training objective; metrics report CE and aux separately."""
    rules = rules_for_mesh(mesh, rules)
    pp = mesh.shape.get("pp", 1)
    shardings = state_specs(config, optimizer, rules, mesh)
    b_sh = batch_sharding(mesh, rules)
    batch_sh = {"tokens": b_sh, "targets": b_sh, "mask": b_sh}
    repl = NamedSharding(mesh, P())

    def loss_fn(params, batch):
        if pp > 1:
            x, aux = llama.forward_pipelined(
                params, batch["tokens"], config, mesh=mesh, rules=rules,
                n_micro=n_micro, attn_impl=attn_impl,
                return_hidden=True, return_aux=True,
            )
        else:
            x, aux = llama.forward(
                params, batch["tokens"], config, mesh=mesh, rules=rules,
                attn_impl=attn_impl, return_hidden=True, return_aux=True,
            )
        head = (
            params["embed"].T if config.tie_embeddings else params["lm_head"]
        ).astype(config.dtype)
        if loss_impl == "chunked":
            loss, _ = chunked_cross_entropy(
                x, head, batch["targets"], batch.get("mask"),
                softcap=config.logit_softcap,
                rules=rules, mesh=mesh,
            )
        else:
            loss, _ = fused_cross_entropy(
                x, head, batch["targets"], batch.get("mask"), rules=rules, mesh=mesh,
                softcap=config.logit_softcap,
            )
        return loss + aux, (loss, aux)

    def grads_of(params, batch):
        return jax.value_and_grad(loss_fn, has_aux=True)(params, batch)

    def accum_grads(params, batch):
        """Scan grad_accum microbatches; weight by each one's mask sum."""
        micro = jax.tree.map(
            lambda a: a.reshape((grad_accum, a.shape[0] // grad_accum) + a.shape[1:]),
            batch,
        )

        def body(carry, mb):
            g_acc, loss_acc, aux_acc, w_acc = carry
            (_, (loss, aux)), g = grads_of(params, mb)
            w = jnp.maximum(mb["mask"].astype(jnp.float32).sum(), 1.0)
            g_acc = jax.tree.map(lambda a, b: a + b * w, g_acc, g)
            return (g_acc, loss_acc + loss * w, aux_acc + aux * w, w_acc + w), None

        zeros = jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), params
        )
        (g, loss, aux, w), _ = jax.lax.scan(
            body, (zeros, jnp.zeros(()), jnp.zeros(()), jnp.zeros(())), micro
        )
        grads = jax.tree.map(lambda a, p: (a / w).astype(p.dtype), g, params)
        return loss / w, aux / w, grads

    def step(state, batch):
        if grad_accum > 1:
            loss, aux, grads = accum_grads(state["params"], batch)
        else:
            (_, (loss, aux)), grads = grads_of(state["params"], batch)
        updates, opt_state = optimizer.update(
            grads, state["opt_state"], state["params"]
        )
        params = optax.apply_updates(state["params"], updates)
        new_state = {
            "params": params,
            "opt_state": opt_state,
            "step": state["step"] + 1,
        }
        gnorm = optax.global_norm(grads)
        return new_state, {"loss": loss, "aux_loss": aux, "grad_norm": gnorm}

    return jax.jit(
        step,
        in_shardings=(shardings, batch_sh),
        out_shardings=(
            shardings,
            {"loss": repl, "aux_loss": repl, "grad_norm": repl},
        ),
        donate_argnums=(0,),
    )


def make_eval_step(
    config: llama.LlamaConfig,
    mesh: Mesh,
    rules: Optional[ShardingRules] = None,
) -> Callable:
    rules = rules or default_rules()

    def step(params, batch):
        logits = llama.forward(params, batch["tokens"], config, mesh=mesh, rules=rules)
        loss, _ = cross_entropy_loss(logits, batch["targets"], batch.get("mask"))
        return {"loss": loss}

    return jax.jit(step)


def flops_per_token(config: llama.LlamaConfig, seq_len: int) -> float:
    """Approximate train FLOPs/token: 6·N *active* params + attention
    term (for MoE only the routed experts' FLOPs count)."""
    n = config.num_active_params()
    attn = 12 * config.n_layers * config.hidden_size * seq_len  # fwd+bwd qk/av
    return 6.0 * n + attn


#: Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
#: 819 GB/s HBM per chip). A device that is not listed is an error,
#: not a default: MFU against an assumed peak is not a measurement.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peak_flops(device_kind: str) -> float:
    """Peak bf16 FLOP/s of one chip of ``device_kind``; raises for a
    device the table does not know."""
    try:
        return DEVICE_PEAKS[device_kind]["bf16_flops"]
    except KeyError:
        raise ValueError(
            f"no published peak for device kind {device_kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)}); add it to "
            "train/step.py DEVICE_PEAKS with its source"
        ) from None


# ---------------------------------------------------------------------------
# step telemetry (obs registry hook)
# ---------------------------------------------------------------------------


def new_train_registry():
    """Registry pre-populated with every train metric family (the
    serve-side twin lives in serve/metrics.py; tools/
    check_metrics_docs.py enumerates both against the docs)."""
    from dstack_tpu.obs import LATENCY_BUCKETS_S, Registry

    r = Registry()
    r.histogram(
        "dtpu_train_step_seconds",
        "Train-step wall time (averaged over the sync window)",
        buckets=LATENCY_BUCKETS_S,
    )
    r.gauge(
        "dtpu_train_tokens_per_sec", "Training throughput over all chips"
    )
    r.gauge(
        "dtpu_train_mfu",
        "Model-FLOPs utilization vs the configured per-chip peak",
    )
    r.counter("dtpu_train_steps_total", "Optimizer steps completed")
    r.counter("dtpu_train_tokens_total", "Tokens consumed by training")
    return r


def make_step_callback(
    config: llama.LlamaConfig,
    tokens_per_step: int,
    seq_len: int,
    peak_flops_per_chip: Optional[float] = None,
    n_chips: int = 1,
    registry=None,
):
    """Step-telemetry hook → ``cb(dt_seconds, steps=1)``.

    The training loop calls it at its host-sync points (finetune syncs
    once per log window — per-step syncing would serialize JAX's async
    dispatch, so ``dt_seconds`` is the window-average step time and
    ``steps`` the window width). Each call observes step time and
    refreshes tokens/sec and MFU; an exporter (or the bench) reads the
    registry. ``peak_flops_per_chip`` comes from :func:`peak_flops` for
    the device the run is on; without one (a CPU run) MFU is neither
    computed nor exported. Returns the callback; the registry rides on
    it as ``cb.registry``."""
    reg = registry if registry is not None else new_train_registry()
    fpt = flops_per_token(config, seq_len)
    step_hist = reg.family("dtpu_train_step_seconds")
    tps_gauge = reg.family("dtpu_train_tokens_per_sec")
    mfu_gauge = reg.family("dtpu_train_mfu")
    steps_ctr = reg.family("dtpu_train_steps_total")
    tokens_ctr = reg.family("dtpu_train_tokens_total")

    def cb(dt_seconds: float, steps: int = 1) -> dict:
        dt = max(float(dt_seconds), 1e-9)
        tps = tokens_per_step / dt
        out = {"tokens_per_sec": tps, "step_time_s": dt}
        for _ in range(steps):
            step_hist.observe(dt)
        tps_gauge.set(round(tps, 3))
        if peak_flops_per_chip:
            out["mfu"] = tps * fpt / (peak_flops_per_chip * max(n_chips, 1))
            mfu_gauge.set(round(out["mfu"], 6))
        steps_ctr.inc(steps)
        tokens_ctr.inc(tokens_per_step * steps)
        return out

    cb.registry = reg
    return cb
