"""A serve cell: the real server in a child, traffic over HTTP from this
process, the plain reference in a second child once the chip is free.

Order of one run: tokenizer directory → server child (``launch.py
serve``: weights, the program's warm-up, the benchmark's warm grid) →
``/health`` (up to here is ``setup_s``) → the ramp, a stretch of the
cell's own traffic → the measured window → SIGTERM, ``device.json`` →
the reference child over a seeded sample of the requests that the
window finished → with ``--trace 1`` the trace child.
"""

import asyncio
import json
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.request

from benchmark import readers, tokenizer
from benchmark.readers import percentile
from benchmark.traffic import client, generate

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
LAUNCH = os.path.join(ROOT, "benchmark", "launch.py")


def parse_prometheus(text: str) -> dict:
    """Exposition text → ``{sample name: sum over its label sets}``."""
    out = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        head, _, value = line.rpartition(" ")
        name = head.split("{", 1)[0]
        try:
            out[name] = out.get(name, 0.0) + float(value)
        except ValueError:
            continue
    return out


def _get(url: str, timeout: float = 5.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode()


def _post(url: str, timeout: float):
    req = urllib.request.Request(url, data=b"", method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read().decode()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _child_env(extra=None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    env.update(extra or {})
    return env


def stop_child(proc, grace: float = 60.0) -> int:
    """SIGTERM, wait, SIGKILL if it must; always reaps."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(grace)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
    return proc.returncode


def wait_healthy(proc, base: str, timeout: float) -> dict:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(f"server child exited with {proc.returncode} before /health")
        try:
            status, body = _get(base + "/health", 2.0)
            if status == 200:
                return json.loads(body)
        except OSError:
            pass
        time.sleep(0.25)
    raise RuntimeError(f"server not healthy within {timeout:.0f} s")


def pick_check_sample(records, t_open, t_close, n: int, seed: int, greedy: bool) -> list:
    """Greedy (or sampled) requests the window finished whole, the
    longest first, the rest drawn from the seed."""
    done = [
        r for r in records
        if (r.req["temperature"] == 0.0) == greedy and r.error is None
        and r.done is not None and t_open <= r.done <= t_close
        and len(r.ids) == r.req["max_tokens"]
    ]
    done.sort(key=lambda r: (-(len(r.req["prompt_ids"]) + len(r.ids)), r.req["rid"]))
    if len(done) <= n:
        return done
    rest = done[1:]
    random.Random(f"{seed}:check:{greedy}").shuffle(rest)
    return done[:1] + rest[: n - 1]


def end_to_end(records, t_open, t_close, seconds, wanted) -> tuple:
    """The client-side metrics, over all the work and all the time of
    the window → (metrics, the window's requests, tokens delivered)."""
    window = [r for r in records if t_open <= r.due < t_close]
    out = {}
    ttft = [
        (r.first - r.due) * 1e3 if (r.first is not None and r.error is None)
        else seconds * 1e3
        for r in window
    ]
    if ttft:
        print(
            f"ttft samples={len(ttft)} mean_ms={sum(ttft) / len(ttft):.1f} "
            + " ".join(f"p{q}_ms={percentile(ttft, q):.1f}" for q in (50, 90, 95, 99))
        )
    gaps, tokens = [], 0
    for r in records:
        prev = None
        for t, n in r.deltas:
            if t_open <= t < t_close:
                tokens += n
                if prev is not None:
                    gaps.append((t - prev) * 1e3)
            prev = t
    if "itl_p95_ms" in wanted and gaps:
        print(
            f"itl samples={len(gaps)} mean_ms={sum(gaps) / len(gaps):.3f} "
            + " ".join(f"p{q}_ms={percentile(gaps, q):.3f}" for q in (50, 90, 95, 99))
        )
        out["itl_p95_ms"] = percentile(gaps, 95)
    if "out_tokens_per_s" in wanted:
        out["out_tokens_per_s"] = tokens / seconds
    return out, window, tokens


def run(args, workload: dict, cfg: dict, cfg_path: str, metric_defs: dict,
        launcher: str = LAUNCH) -> dict:
    t_start = time.monotonic()
    mix = dict(workload["traffic"])
    if getattr(args, "rate", None):
        mix["rate_rps"] = args.rate  # the sweep that finds the knee (PERF.md §4)
    chips = int(workload.get("chips", 1))
    rehearsal = bool(args.platform)
    out_dir = os.path.join(
        ROOT, ".bench_runs", f"{workload['name']}-{args.seed}-{args.trace}"
    )
    os.makedirs(out_dir, exist_ok=True)
    vocab = cfg["vocab_size"]
    tok_dir = tokenizer.write_tokenizer_dir(
        os.path.join(ROOT, ".bench_runs", f"tokenizer-{vocab}"), vocab
    )
    plan = generate.generate(mix, vocab, args.seed, args.seconds)
    port = _free_port()
    base = f"http://127.0.0.1:{port}"
    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    cmd = [
        sys.executable, launcher, "serve", "--config", cfg_path,
        "--seed", str(args.seed), "--port", str(port), "--tokenizer", tok_dir,
        "--out", out_dir, "--chips", str(chips),
    ]
    if args.platform:
        cmd += ["--platform", args.platform]
    cmd += ["--warm-traffic", json.dumps({
        "prompt_tokens": mix["prompt_tokens"], "temperature": mix.get("temperature", 0.0),
    })]
    control = getattr(args, "control", None)
    if control == "int8":
        cmd += ["--quantize", "int8"]
    for stale in ("warm.json", "device.json"):
        if os.path.exists(os.path.join(out_dir, stale)):
            os.remove(os.path.join(out_dir, stale))
    env = _child_env({"DTPU_PROFILER_DIR": trace_dir} if args.trace else None)
    log = open(os.path.join(out_dir, "server.log"), "w")
    proc = subprocess.Popen(cmd, stdout=log, stderr=subprocess.STDOUT, env=env, cwd=ROOT)
    prom = {}
    trace_span = {}
    try:
        health = wait_healthy(proc, base, float(workload.get("boot_timeout_s", 1100)))
        t_ready = time.monotonic()
        setup_s = t_ready - t_start
        if not os.path.exists(os.path.join(out_dir, "warm.json")):
            raise RuntimeError("the server came up without the benchmark's warm grid")

        async def scrape(key):
            loop = asyncio.get_running_loop()
            _, text = await loop.run_in_executor(None, _get, base + "/metrics", 10.0)
            prom[key] = parse_prometheus(text)

        async def profiler(action):
            loop = asyncio.get_running_loop()
            trace_span[action] = time.monotonic()
            await loop.run_in_executor(
                None, _post, f"{base}/debug/profiler/{action}", 600.0
            )
            trace_span[action + "_done"] = time.monotonic()

        marks = [
            (-plan["ramp_s"], lambda: scrape("ready")),
            (0.0, lambda: scrape("before")), (args.seconds, lambda: scrape("after")),
        ]
        if args.trace:
            trace_s = min(float(workload.get("trace_s", 4.0)), args.seconds / 2)
            marks += [
                (args.seconds - trace_s, lambda: profiler("start")),
                (args.seconds + 1e-3, lambda: profiler("stop")),
            ]
        res = asyncio.run(client.run_traffic(
            base, cfg["name"], plan, args.seconds,
            float(workload.get("drain_s", 3.0)), marks,
        ))
    finally:
        rc = stop_child(proc)
        log.close()
    print(f"server child exit={rc} health_device={json.dumps(health.get('device'))}")
    with open(os.path.join(out_dir, "device.json")) as f:
        device = json.load(f)
    records, t_open, t_close = res["records"], res["t_open"], res["t_close"]
    wanted = set(workload["end_to_end"])
    e2e, window, tokens = end_to_end(records, t_open, t_close, args.seconds, wanted)
    e2e["setup_s"] = setup_s
    failed = [r for r in window if r.error is not None]
    for r in failed[:5]:
        print(f"failed {r.req['rid']}: {r.error}")
    # -- correctness: numbers beside their limits
    spec = workload["check"]
    limits = spec["limits"]
    finished = [
        r for r in records if r.done is not None and r.error is None
        and t_open <= r.done <= t_close
    ]
    short = sum(1 for r in finished if len(r.ids) != r.req["max_tokens"])
    compared = {"token_count_mismatches": (short, 0)}
    sample = pick_check_sample(records, t_open, t_close, int(spec["requests"]), args.seed, True)
    sampled = pick_check_sample(
        records, t_open, t_close, int(spec.get("sampled_requests", 0)), args.seed, False
    )
    check = None
    if sample:
        req_path = os.path.join(out_dir, "check_requests.json")
        res_path = os.path.join(out_dir, "check_result.json")
        with open(req_path, "w") as f:
            json.dump([
                {"rid": r.req["rid"], "prompt_ids": r.req["prompt_ids"], "ids": r.ids,
                 "temperature": r.req["temperature"]}
                for r in sample + sampled
            ], f)
        ccmd = [
            sys.executable, launcher, "check", "--config", cfg_path,
            "--seed", str(args.seed), "--requests", req_path, "--result", res_path,
            "--chips", str(chips),
        ]
        if args.platform:
            ccmd += ["--platform", args.platform]
        if control == "int8-reference":
            ccmd += ["--control", "int8"]
        t_c = time.monotonic()
        with open(os.path.join(out_dir, "check.log"), "w") as clog:
            crc = subprocess.run(
                ccmd, stdout=clog, stderr=subprocess.STDOUT, env=_child_env(), cwd=ROOT
            ).returncode
        if crc != 0:
            raise RuntimeError(f"reference child exited with {crc} (see {out_dir}/check.log)")
        with open(res_path) as f:
            check = json.load(f)
        print(
            f"reference_s={time.monotonic() - t_c:.2f} greedy_requests={len(sample)} "
            f"positions={check['positions']} agree="
            f"{sum(r['agree'] * r['served_tokens'] for r in check['requests']) / check['positions']:.4f}"
            f" seconds={json.dumps(check.get('seconds'))}"
        )
        compared["served_gap_max"] = (check["gap_max"], limits["served_gap_max"])
        compared["served_gap_mean"] = (check["gap_mean"], limits["served_gap_mean"])
        if check.get("sound"):
            # a reference-control run reads the served tokens too (one boot, both readings)
            print(f"sound served_gap_max={check['sound']['gap_max']!r} "
                  f"served_gap_mean={check['sound']['gap_mean']!r}")
        if "sampled" in check:
            got = check["sampled"]
            sent = str(got["temperature"])
            compared["sampled_excess_abs"] = (abs(got["excess"][sent]), limits["sampled_excess_abs"])
            print(f"sampled requests={len(sampled)} positions={got['positions']} "
                  f"excess_by_assumed_temperature={json.dumps(got['excess'])}")
        elif sampled or "sampled_excess_abs" in limits:
            compared["sampled_excess_abs"] = (float("inf"), limits["sampled_excess_abs"])
    else:
        print("check: the window finished no greedy request to compare")
    correct = check is not None
    for name, (value, limit) in compared.items():
        ok = value <= limit
        correct = correct and ok
        print(f"compared {name}={value!r} limit={limit!r} {'ok' if ok else 'EXCEEDED'}")
    # -- the trace, reduced by a CPU-only child
    trace = None
    if args.trace:
        tres = os.path.join(out_dir, "trace_result.json")
        tcmd = [sys.executable, launcher, "trace", "--dir", trace_dir,
                "--result", tres, "--chips", str(chips)]
        with open(os.path.join(out_dir, "trace.log"), "w") as tlog:
            trc = subprocess.run(
                tcmd, stdout=tlog, stderr=subprocess.STDOUT,
                env=_child_env({"JAX_PLATFORMS": "cpu"}), cwd=ROOT,
            ).returncode
        shutil.rmtree(trace_dir, ignore_errors=True)  # tens of MB a capture
        if trc == 0:
            with open(tres) as f:
                trace = json.load(f)
        elif not rehearsal:
            raise RuntimeError(f"trace child exited with {trc} (see {out_dir}/trace.log)")
    ctx = {
        "prom_before": prom.get("before", {}), "prom_after": prom.get("after", {}),
        "records": records, "window": (t_open, t_close), "seconds": args.seconds,
        "trace": trace, "trace_span": trace_span, "config": cfg,
        "workload": workload, "device": device, "tokens_in_window": tokens,
    }
    def delta(name, a="before", b="after"):
        return prom.get(b, {}).get(name, 0.0) - prom.get(a, {}).get(name, 0.0)

    def mean_ms(hist):
        n = delta(hist + "_count")
        return delta(hist + "_sum") / n * 1e3 if n else None

    print(f"tokens client={tokens} server_counter_delta={delta('dtpu_serve_tokens_generated_total'):.0f}")
    compiles = delta("dtpu_serve_compiles_total")
    print("info " + json.dumps({
        "rate_rps": mix.get("rate_rps"), "attempted": len(window),
        "completed_in_window": sum(
            1 for r in records if r.done is not None and t_open <= r.done < t_close
        ),
        "queue_wait_mean_ms": mean_ms("dtpu_serve_queue_wait_seconds"),
        "engine_step_ms": mean_ms("dtpu_serve_decode_step_seconds"),
        "compiles_in_ramp": delta("dtpu_serve_compiles_total", "ready", "before"),
        "compiles_in_window": compiles,
    }))
    if compiles:
        print(f"WARNING: {compiles:.0f} programs compiled inside the window", file=sys.stderr)
    metrics = {}
    if args.trace:
        for name, m in metric_defs.items():
            if rehearsal and m["source"] != "program_counter":
                continue
            value = readers.read(m, ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": m["unit"]}
    elif not rehearsal:
        units = workload["end_to_end"]
        metrics = {k: {"value": v, "unit": units[k]} for k, v in e2e.items() if k in units}
    line = {
        "correct": bool(correct), "attempted": len(window), "failed": len(failed),
        "metrics": metrics, "device": dict(device), "compiles_in_window": compiles,
    }
    if rehearsal:
        line["rehearsal"] = True
    if trace is not None:
        line["device"]["busy_s"] = trace["busy_s"]
        line["device"]["window_s"] = trace["window_s"]
        line["breakdown"] = trace["breakdown"]
    return line
