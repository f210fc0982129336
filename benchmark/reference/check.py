"""Served tokens against the plain reference.

For each sampled request the reference runs once over prompt + served
tokens (teacher forcing, the whole sequence in one float32 forward, no
cache) and reads, at every served position, the gap by which the served
token's logit lies below the reference's best logit there. A sound
greedy server picks the reference's best token or one within rounding
of it, through prefill and through the cache alike; a wrong cache row,
a dropped expert token or a lower precision shows as a wide gap.

With ``control`` the served tokens are ignored: at the same positions of
the same prompts and tokens, the token that the lower precision puts
first is read against the float32 logits instead.
"""

import importlib

import numpy as np


def _gaps(ref, cfg, params, tokens, n_prompt, n_served, control, hid=None):
    """→ gaps [n_served] (float32, ≥ 0) and the share of positions whose
    token equals the reference's best."""
    if hid is None:
        hid = ref.hidden_states(cfg, params, tokens)
    # position p predicts token p+1: served token j sits at n_prompt + j
    pos = np.arange(n_prompt - 1, n_prompt - 1 + n_served)
    # few head shapes: pad the positions to a multiple of 256 (repeats, cut below)
    pos = np.concatenate([pos, np.full(-n_served % 256, pos[-1])])
    if control:
        hid_c = ref.hidden_states(cfg, params, tokens, control)
        _, picked, _ = ref.head(cfg, params, hid_c[pos], np.zeros((len(pos), 1)), control)
        picked = np.asarray(picked)
    else:
        picked = np.asarray(tokens)[pos + 1]
    best, best_id, vals = ref.head(cfg, params, hid[pos], picked[:, None])
    gaps = (np.asarray(best) - np.asarray(vals)[:, 0])[:n_served]
    best_id, picked = np.asarray(best_id)[:n_served], picked[:n_served]
    return gaps, float((best_id == picked).mean()), hid


def _summary(per, all_gaps, control):
    allg = np.concatenate(all_gaps) if all_gaps else np.zeros(0)
    return {
        "control": control,
        "positions": int(allg.size),
        "gap_max": float(allg.max()) if allg.size else None,
        "gap_mean": float(allg.mean()) if allg.size else None,
        "requests": per,
    }


def _sampled(ref, cfg, params, rows, temperatures) -> dict:
    """``rows``: (hidden [n, H], served ids [n]) of every sampled
    request → for each temperature the mean over all positions of
    (served token's logit - the distribution's mean logit) / T."""
    import jax

    from . import common as C

    hid = np.concatenate([h for h, _ in rows])
    ids = np.concatenate([i for _, i in rows])
    n = len(ids)
    pad = -n % 256
    hid = np.concatenate([hid, np.repeat(hid[-1:], pad, 0)])
    ids = np.concatenate([ids, np.repeat(ids[-1:], pad)])
    out = {"positions": n, "excess": {}}
    with jax.default_matmul_precision("highest"):
        for t in temperatures:
            got, mean = C.sampling_stats(
                hid, ref.final_norm(cfg, params), params["lm_head"], ids, float(t)
            )
            out["excess"][str(t)] = float((np.asarray(got) - np.asarray(mean))[:n].mean())
    return out


#: the sampler's statistic is read a second time as if the requests had
#: been sent with their temperature over this: what a server that sampled
#: that much sharper than asked would read (0.7 → 1.0)
FAULT_SHARPER = 0.7


def run(cfg: dict, seed: int, requests: list, control=None) -> dict:
    """``requests``: ``[{"rid", "prompt_ids", "ids", "temperature"}]`` →
    gap statistics over the greedy ones and, where some were sampled,
    the sampler's statistic at the temperature they were sent with and
    at the fault's (``FAULT_SHARPER``). Weights are made here, from the seed. With
    ``control`` the gaps are the control's, and the served tokens'
    reading rides along under ``"sound"`` (one forward serves both)."""
    import time

    import jax

    from benchmark import weights

    t0 = time.monotonic()
    ref = importlib.import_module(f"benchmark.reference.{cfg['reference']}")
    params = weights.make_params(cfg, seed)
    jax.block_until_ready(params)
    t1 = time.monotonic()
    modes = [None, control] if control else [None]
    per = {m: [] for m in modes}
    gaps_of = {m: [] for m in modes}
    sampled_rows, temps = [], set()
    for r in requests:
        tokens = list(r["prompt_ids"]) + list(r["ids"])
        # pad to a multiple of 512 so few shapes compile; causal
        # attention and per-token routing leave earlier positions alone
        padded = np.asarray(tokens + [1] * (-len(tokens) % 512))
        n_prompt, n_served = len(r["prompt_ids"]), len(r["ids"])
        if r.get("temperature", 0.0) > 0:
            hid = np.asarray(ref.hidden_states(cfg, params, padded))
            sampled_rows.append(
                (hid[n_prompt - 1:n_prompt - 1 + n_served], np.asarray(r["ids"]))
            )
            temps.add(r["temperature"])
            continue
        hid = None
        for m in modes:
            gaps, agree, hid = _gaps(ref, cfg, params, padded, n_prompt, n_served, m, hid)
            per[m].append({
                "rid": r["rid"], "prompt_tokens": n_prompt,
                "served_tokens": n_served, "gap_max": float(gaps.max()),
                "gap_mean": float(gaps.mean()), "agree": agree,
            })
            gaps_of[m].append(gaps)
    out = _summary(per[modes[-1]], gaps_of[modes[-1]], control)
    if control:
        out["sound"] = _summary(per[None], gaps_of[None], None)
    if sampled_rows:
        (sent,) = temps  # one temperature a mix
        out["sampled"] = _sampled(ref, cfg, params, sampled_rows, [sent, sent / FAULT_SHARPER])
        out["sampled"]["temperature"] = sent
    out["seconds"] = {"weights": t1 - t0, "forward": time.monotonic() - t1}
    return out
