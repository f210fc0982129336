"""(traffic mix, seed, seconds) → the requests of one run. Pure, stdlib.

One general generator reads every mix; a mix is the ``traffic`` group of
a cell's file under ``benchmark/workloads/``. Every draw comes from a
named ``random.Random("{seed}:{stream}")`` (the program's loadgen
idiom), so streams never perturb each other.

Mix keys:

- ``loop``: ``"open"`` (arrivals on a schedule, whatever the server is
  doing) or ``"closed"`` (``clients`` callers, each sends its next
  request when the last one completes).
- ``arrivals`` (open loop): ``"poisson"``: independent exponential gaps
  at ``rate_rps``, drawn from the seed, one process through ramp and
  window. How many requests fall due in the window is the draw's.
- ``prompt_tokens``: ``[lo, hi]`` with ``prompt_dist`` ``"loguniform"``
  or ``"uniform"``; ``output_tokens``: ``[lo, hi]``, uniform.
- ``lengths``: ``"iid"``: every request draws its lengths independently
  from the seed. ``"stratified"``: every seed gets the same multiset
  (the distributions' quantile midpoints) in another order, dealt into
  blocks of ``stratify_block`` requests that each span the whole range
  (see ``_stratified``); for closed loops, where the offered work
  should not depend on the seed.
- ``temperature``; ``greedy_every``: one request in n, at an offset
  drawn from the seed, is sent at temperature 0.
- ``ramp_s``: the same traffic runs this long before the window opens,
  so the queue and the batch are in steady state when timing starts.
"""

import math
import random

#: no closed-loop client streams faster than this, which bounds how many
#: requests one client can finish and so how many it is dealt
CLIENT_TOKENS_PER_S_MAX = 200.0


def _rng(seed: int, stream: str) -> random.Random:
    return random.Random(f"{seed}:{stream}")


def _at_quantile(bounds, q: float, dist: str) -> int:
    lo, hi = bounds
    if dist == "loguniform":
        v = math.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
    elif dist == "uniform":
        v = lo + q * (hi - lo)
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    return max(lo, min(hi, int(round(v))))


def _stratified(values: list, rng: random.Random, block: int) -> list:
    """Seeded order of ``values``: the sorted values are dealt
    round-robin into blocks of about ``block``, so that every block
    holds one value of each quantile band; order inside a block and the
    order of the blocks are the seed's."""
    n_blocks = max(1, round(len(values) / max(1, block)))
    blocks = [[] for _ in range(n_blocks)]
    for i, v in enumerate(sorted(values)):
        blocks[i % n_blocks].append(v)
    for b in blocks:
        rng.shuffle(b)
    rng.shuffle(blocks)
    return [v for b in blocks for v in b]


def _lengths(mix: dict, key: str, dist: str, n: int, seed: int, phase: str) -> list:
    rng = _rng(seed, f"{phase}:{key}")
    kind = mix["lengths"]
    if kind == "iid":
        return [_at_quantile(mix[key], rng.random(), dist) for _ in range(n)]
    if kind == "stratified":
        mids = [_at_quantile(mix[key], (i + 0.5) / n, dist) for i in range(n)]
        return _stratified(mids, rng, int(mix["stratify_block"]))
    raise ValueError(f"unknown lengths {kind!r}")


def _requests(mix: dict, vocab: int, seed: int, n: int, phase: str) -> list:
    """n requests without times."""
    prompts = _lengths(mix, "prompt_tokens", mix.get("prompt_dist", "loguniform"), n, seed, phase)
    outputs = _lengths(mix, "output_tokens", "uniform", n, seed, phase)
    every = int(mix.get("greedy_every", 0))
    offset = _rng(seed, f"{phase}:greedy").randrange(every) if every else 0
    temperature = float(mix.get("temperature", 0.0))
    tok = _rng(seed, f"{phase}:tokens")
    samp = _rng(seed, f"{phase}:sampling")
    out = []
    for i in range(n):
        greedy = temperature == 0.0 or (every > 0 and i % every == offset)
        t = 0.0 if greedy else temperature
        out.append({
            "rid": f"{phase}{i}",
            "phase": phase,
            # id 0 is the tokenizer's unknown word: never sent
            "prompt_ids": [tok.randrange(1, vocab) for _ in range(prompts[i])],
            "max_tokens": outputs[i],
            "temperature": t,
            "seed": samp.randrange(1, 2**31) if t > 0 else None,
        })
    return out


def _arrival_times(mix: dict, seed: int, t0: float, t1: float) -> list:
    """Arrival times in ``[t0, t1)``."""
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    rng, rate = _rng(seed, "arrivals"), float(mix["rate_rps"])
    times, t = [], t0 + rng.expovariate(rate)
    while t < t1:
        times.append(t)
        t += rng.expovariate(rate)
    return times


def generate(mix: dict, vocab: int, seed: int, seconds: float) -> dict:
    """→ ``{"loop", "ramp_s", "requests": [...]}``. Open loop: each
    request has ``due_s`` relative to the window's opening (negative in
    the ramp). Closed loop: each has ``client`` and ``order``; a
    client's list is long enough to outlast ramp + window."""
    loop = mix["loop"]
    ramp = float(mix.get("ramp_s", 0.0))
    if loop == "open":
        times = _arrival_times(mix, seed, -ramp, seconds)
        reqs = _requests(mix, vocab, seed, len(times), "win")
        for r, t in zip(reqs, times):
            r["due_s"] = t
            if t < 0:
                r["phase"] = "ramp"
        return {"loop": loop, "ramp_s": ramp, "requests": reqs}
    if loop == "closed":
        clients = int(mix["clients"])
        per_client = 1 + math.ceil(
            (ramp + seconds) * CLIENT_TOKENS_PER_S_MAX / mix["output_tokens"][0]
        )
        reqs = _requests(mix, vocab, seed, clients * per_client, "win")
        for i, r in enumerate(reqs):
            r["client"], r["order"] = i % clients, i // clients
        # clients start spread over the ramp, so they do not finish in step
        starts = [ramp * c / clients for c in range(clients)]
        _rng(seed, "client_starts").shuffle(starts)
        return {
            "loop": loop, "ramp_s": ramp, "requests": reqs,
            "client_start_s": [s - ramp for s in starts],
        }
    raise ValueError(f"unknown loop {loop!r}")
