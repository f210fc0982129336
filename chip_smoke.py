#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives the serve path and the train path of Llama-3.2-1B (published
widths, bf16, random init from seed 0) on ONE TPU chip through the
entry points a user calls, checks what comes out, and prints one JSON
line per phase and a last line::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The chip belongs to one process at a time, so this parent is
stdlib-only and NEVER imports jax (nor any module that does). Each
phase is a child process; the parent waits for it to exit, and checks
its port is closed, before the next one starts. The first failing phase
ends the run with a non-zero exit and no result line.

    python chip_smoke.py               # one chip: device, serve, numerics, train
    python chip_smoke.py --chips 4     # one four-chip host: ONLY the paths that
                                       # exist across chips, and what each is
                                       # compared with (tp serving, sharded
                                       # training, one replica per chip)
    python chip_smoke.py --rehearse    # llama-tiny on the CPU: control flow only.
                                       # Every line says "platform": "cpu" and the
                                       # last line has no "ok" key: a rehearsal
                                       # can never be read as a chip pass.

Children keep compiled programs in the cache ``dstack_tpu.utils.backend``
places (``JAX_COMPILATION_CACHE_DIR``, else ``.jax_compile_cache/`` in
the checkout); each phase line carries the number of cache entries
before and after, so a second run on the same machine shows hits.
Full child logs land in ``chiprun_out/chip_smoke/``.
"""

import argparse
import collections
import http.client
import json
import math
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
MODEL, REHEARSE_MODEL = "llama-3.2-1b", "llama-tiny"
VOCAB = {MODEL: 128256, REHEARSE_MODEL: 512}
SERVER = [sys.executable, "-m", "dstack_tpu.serve.openai_server"]
FINETUNE = [sys.executable, "-m", "dstack_tpu.train.finetune"]
# printable ASCII forced by a +100 logit bias: with the byte tokenizer
# over a 128k random-init vocab almost no sampled id decodes to text,
# so requests whose TEXT is compared confine sampling to visible bytes
ASCII_BIAS = {str(i): 100 for i in range(32, 127)}
# stated margins
TP_LOGPROB_MARGIN = 0.15  # nats, tp=4 vs tp=1 chosen-token logprob (bf16)
SHARDED_LOSS_MARGIN = 0.05  # step-1 loss, sharded vs one chip (bf16)


class PhaseFailed(Exception):
    pass


class Smoke:
    def __init__(self, rehearse: bool, chips: int):
        self.rehearse = rehearse
        self.chips = chips
        self.model = REHEARSE_MODEL if rehearse else MODEL
        self.want = "cpu" if rehearse else "tpu"
        self.platform_flag = ["--platform", "cpu"] if rehearse else []
        self.env = dict(os.environ, PYTHONUNBUFFERED="1")
        if rehearse:
            self.env["JAX_PLATFORMS"] = "cpu"
            # XLA:CPU logs a screenful per persistent-cache load
            self.env.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
            self.env["XLA_FLAGS"] = (
                self.env.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={chips}"
            ).strip()
        self.work = tempfile.mkdtemp(prefix="chip_smoke_")
        self.procs: list = []
        self.cache_dir = None  # the device phase reports it
        self.device = None
        os.makedirs(LOG_DIR, exist_ok=True)

    # ---- processes ------------------------------------------------------

    def spawn(self, argv, log_name, stdout=None):
        log = open(os.path.join(LOG_DIR, log_name), "w")
        proc = subprocess.Popen(
            argv, cwd=HERE, env=self.env, stdout=stdout or log, stderr=log,
            text=True, start_new_session=True,
        )
        proc.log_path = log.name
        log.close()
        self.procs.append(proc)
        return proc

    def run(self, argv, log_name, timeout):
        """Run a child to its end → its stdout; PhaseFailed on a
        non-zero exit or a timeout, with the tail of its stderr."""
        proc = self.spawn(argv, log_name, stdout=subprocess.PIPE)
        try:
            out, _ = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.stop(proc)
            raise PhaseFailed(
                f"{log_name}: no exit within {timeout}s\n" + tail(proc.log_path)
            )
        with open(proc.log_path, "a") as f:
            f.write("\n---- stdout ----\n" + out)
        if proc.returncode != 0:
            raise PhaseFailed(
                f"{log_name}: exit {proc.returncode}\n"
                + tail(proc.log_path, skip_after="---- stdout ----")
                + "\n" + out[-1500:]
            )
        return out

    def stop(self, proc, grace=60.0):
        """SIGTERM, then SIGKILL the whole session; → exited in time."""
        if proc.poll() is not None:
            return True
        try:
            os.killpg(proc.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        try:
            proc.wait(timeout=grace)
            return True
        except subprocess.TimeoutExpired:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait(timeout=30)
            return False

    def close(self):
        for proc in self.procs:
            self.stop(proc, grace=10.0)
        shutil.rmtree(self.work, ignore_errors=True)

    def cache_entries(self):
        if not self.cache_dir or not os.path.isdir(self.cache_dir):
            return 0
        return len(os.listdir(self.cache_dir))

    # ---- phases ---------------------------------------------------------

    def phase(self, name, fn):
        before = self.cache_entries()
        t0 = time.monotonic()
        line = {"phase": name, "ok": True, "rehearse": self.rehearse}
        try:
            line.update(fn())
        except PhaseFailed as e:
            line.update(ok=False, error=str(e)[-4000:])
        line["wall_s"] = round(time.monotonic() - t0, 1)
        line["cache_entries"] = [before, self.cache_entries()]
        if line["ok"] and line.get("platform") != self.want:
            line.update(
                ok=False,
                error=f"ran on {line.get('platform')!r}, not {self.want!r}",
            )
        print(json.dumps(line), flush=True)
        if not line["ok"]:
            raise SystemExit(1)

    def child(self, name, timeout):
        """One of this file's own jax-touching children → its JSON."""
        return last_json(self.run(
            [sys.executable, __file__, "--child", name]
            + (["--rehearse"] if self.rehearse else []),
            f"{name}.log", timeout,
        ))

    def device_phase(self):
        info = self.child("device", timeout=300)
        self.cache_dir = info["compile_cache"]
        self.device = {k: info[k] for k in ("platform", "kind", "count")}
        if info["count"] != self.chips:
            raise PhaseFailed(
                f"--chips {self.chips} but jax sees {info['count']} device(s)"
            )
        return info

    # ---- serve ----------------------------------------------------------

    def start_server(self, flags, log_name, ready_timeout):
        port = free_port()
        proc = self.spawn(
            SERVER + ["--model", self.model, "--port", str(port)]
            + flags + self.platform_flag,
            log_name,
        )
        deadline = time.monotonic() + ready_timeout
        while True:
            if proc.poll() is not None:
                raise PhaseFailed(
                    f"server exited {proc.returncode} before ready\n"
                    + tail(proc.log_path)
                )
            try:
                status, health = http_json("GET", port, "/health", timeout=5)
                if status == 200 and health.get("status") == "ok":
                    return proc, port, health
            except OSError:
                pass
            if time.monotonic() > deadline:
                self.stop(proc)
                raise PhaseFailed(
                    f"server not ready within {ready_timeout}s\n"
                    + tail(proc.log_path)
                )
            time.sleep(1.0)

    def stop_server(self, proc, port):
        if not self.stop(proc):
            raise PhaseFailed("server ignored SIGTERM for 60s (killed)")
        if port_open(port):
            raise PhaseFailed(f"port {port} still open after server exit")

    def serve_phase(self):
        proc, port, health = self.start_server(
            ["--max-batch", "16", "--max-seq", "2048"], "serve.log",
            ready_timeout=300 if self.rehearse else 900,
        )
        try:
            info = self._drive_server(port, health)
        except PhaseFailed as e:
            self.stop(proc)
            raise PhaseFailed(f"{e}\n{tail(proc.log_path)}")
        self.stop_server(proc, port)
        return info

    def _drive_server(self, port, health):
        model = self.model
        _, boot = http_json("GET", port, "/debug/boot?limit=1")
        m0 = metrics(port)
        t0 = time.monotonic()

        def chat():
            return expect_tokens(http_json("POST", port, "/v1/chat/completions", {
                "model": model, "max_tokens": 64, "temperature": 0,
                "messages": [{"role": "user", "content": words(1, 120)}],
            }), 64)["choices"][0]["message"]["content"]

        def completion(body):
            return expect_tokens(http_json("POST", port, "/v1/completions", {
                "model": model, **body,
            }), body["max_tokens"])["choices"][0]

        # (a) one non-streaming greedy chat completion
        text_a = chat()
        # (b) eight concurrent streams: packed prefill, continuous
        # batching, the greedy macro-step decode
        gen0 = metrics(port)["dtpu_serve_tokens_generated_total"]
        t_b = time.monotonic()
        streams = concurrently([
            lambda i=i: stream(port, "/v1/chat/completions", {
                "model": model, "max_tokens": 128, "temperature": 0,
                "stream": True,
                "messages": [{"role": "user", "content": words(10 + i, 512)}],
            })
            for i in range(8)
        ])
        wall_b = time.monotonic() - t_b
        for s in streams:
            if s["status"] != 200 or s["error"] or s["finish"] != "length":
                raise PhaseFailed(f"stream failed: {s}")
        made = metrics(port)["dtpu_serve_tokens_generated_total"] - gen0
        if made != 8 * 128:
            raise PhaseFailed(f"8 streams of 128 tokens generated {made}")
        # (c) one ~1k-token prompt twice: the second rides the prefix cache
        hits0 = metrics(port)["dtpu_serve_prefix_hits_total"]
        long_prompt = {"prompt": words(30, 1024), "max_tokens": 8, "temperature": 0}
        completion(long_prompt)
        completion(long_prompt)
        hits = metrics(port)["dtpu_serve_prefix_hits_total"] - hits0
        if hits < 1:
            raise PhaseFailed("second 1k-token prompt raised no prefix hit")
        # (d) one seeded sampled request twice (visible text, logprobs)
        sampled = {
            "prompt": words(40, 200), "max_tokens": 32, "temperature": 0.8,
            "seed": 7, "logprobs": 1, "logit_bias": ASCII_BIAS,
        }
        d1, d2 = completion(sampled), completion(sampled)
        if d1["text"] != d2["text"] or len(d1["text"]) != 32:
            raise PhaseFailed(f"seeded sampling differs: {d1['text']!r} {d2['text']!r}")
        if d1["logprobs"]["token_logprobs"] != d2["logprobs"]["token_logprobs"]:
            raise PhaseFailed("seeded sampling: logprobs differ between runs")
        # (e) (a) again
        if chat() != text_a:
            raise PhaseFailed("repeated greedy chat completion differs")
        wall = time.monotonic() - t0
        m1 = metrics(port)
        errors = m1["dtpu_serve_request_errors_total"] - m0["dtpu_serve_request_errors_total"]
        if errors:
            raise PhaseFailed(f"{errors} request error(s) counted by the server")
        manifest = boot.get("compile_manifest", {})
        total = m1["dtpu_serve_tokens_generated_total"] - m0["dtpu_serve_tokens_generated_total"]
        return {
            "platform": health["device"]["platform"],
            "device": health["device"],
            "boot_stage_seconds": boot.get("summary", {}).get("stages"),
            "boot_manifest_variants": len(manifest.get("variants", [])),
            "compiles_by_fn": by_label(m1, "dtpu_serve_compiles_total", "fn"),
            "warmup_gap_compiles": m1["dtpu_serve_warmup_gap_compiles_total"],
            "prefix_hits": hits,
            "sampled_text": d1["text"],
            # information, not a claim: every request of the phase, and
            # the eight-stream burst alone, over the client's wall clock
            "throughput_info": {
                "measured_on": health["device"],
                "generated_tokens": total,
                "tokens_per_s_wall": round(total / wall, 1),
                "burst_tokens_per_s_wall": round(8 * 128 / wall_b, 1),
            },
        }

    # ---- numerics -------------------------------------------------------

    def numerics_phase(self):
        info = self.child("numerics", timeout=900)
        if not info.pop("pass"):
            raise PhaseFailed(json.dumps(info))
        return info

    # ---- train ----------------------------------------------------------

    def finetune(self, flags, log_name, timeout=900):
        out_dir = os.path.join(self.work, log_name.replace(".log", ""))
        out = self.run(
            FINETUNE + ["--model", self.model, "--log-every", "1",
                        "--out", out_dir] + flags + self.platform_flag,
            log_name, timeout,
        )
        losses = [float(x) for x in re.findall(r"step \d+/\d+ loss=(\S+)", out)]
        steps = int(flags[flags.index("--steps") + 1])
        bound = 1.5 * math.log(VOCAB[self.model])
        if len(losses) != steps or not all(
            math.isfinite(x) and 0 < x <= bound for x in losses
        ):
            raise PhaseFailed(
                f"{log_name}: losses {losses}, want {steps} finite values "
                f"<= 1.5*ln(vocab) = {bound:.2f}"
            )
        if not os.listdir(out_dir):
            raise PhaseFailed(f"{log_name}: wrote no weights")
        shutil.rmtree(out_dir)
        device = json.loads(re.search(r"device=(\{.*?\})", out).group(1))
        in_use = re.search(r"device_bytes_in_use=(\[.*?\])", out)
        return {
            "losses": losses,
            "last_step": re.findall(r"^step \d+/\d+ .*$", out, re.M)[-1],
            "device": device,
            "device_bytes_in_use": json.loads(in_use.group(1)) if in_use else None,
        }

    def train_phase(self):
        seq = "64" if self.rehearse else "1024"
        full_flags = ["--full", "--batch", "8", "--seq-len", seq, "--steps", "6"]
        finding = None
        try:
            full = self.finetune(full_flags, "train_full.log")
        except PhaseFailed as e:
            if not re.search(r"RESOURCE_EXHAUSTED|[Oo]ut of memory", str(e)):
                raise
            # a finding, not a pass: f32 Adam state does not fit; say so
            finding = "full f32 Adam state did not fit: " + str(e)[-1200:]
            full = self.finetune(
                full_flags + ["--opt-bits", "8"], "train_full_opt8.log"
            )
        lora = self.finetune(
            ["--steps", "3"] + (["--seq-len", seq] if self.rehearse else []),
            "train_lora.log",
        )
        return {
            "platform": full["device"]["platform"],
            "full": full, "lora": lora,
            "full_opt_bits": 8 if finding else 32,
            **({"finding": finding} if finding else {}),
        }

    # ---- four chips -----------------------------------------------------

    def serve_tp_phase(self):
        """``--tp 4`` against ``--tp 1``: the same five greedy prompts."""
        prompts = [words(50 + i, 300) for i in range(5)]
        # llama-tiny has two KV heads: the rehearsal can only split in two
        wide = 2 if self.rehearse else self.chips
        runs = {}
        for tp in (wide, 1):
            # --no-warmup: only the variants these prompts hit compile
            proc, port, health = self.start_server(
                ["--tp", str(tp), "--no-warmup"], f"serve_tp{tp}.log",
                ready_timeout=600,
            )
            try:
                if health["device"]["count"] != tp:
                    raise PhaseFailed(f"--tp {tp}: /health says {health['device']}")
                outs = []
                for p in prompts:
                    choice = expect_tokens(http_json("POST", port, "/v1/completions", {
                        "model": self.model, "prompt": p, "max_tokens": 32,
                        "temperature": 0, "logprobs": 1, "logit_bias": ASCII_BIAS,
                    }, timeout=600), 32)["choices"][0]
                    outs.append((choice["text"], choice["logprobs"]["token_logprobs"]))
                runs[tp] = {"outs": outs, "device": health["device"]}
            except PhaseFailed as e:
                self.stop(proc)
                raise PhaseFailed(f"{e}\n{tail(proc.log_path)}")
            self.stop_server(proc, port)
        equal, worst = 0, 0.0
        for (ta, la), (tb, lb) in zip(runs[wide]["outs"], runs[1]["outs"]):
            # identical up to k; at k (if any) the two runs chose
            # different tokens — allowed only as a near tie
            k = next((i for i, (x, y) in enumerate(zip(ta, tb)) if x != y), len(ta))
            equal += k == len(ta) == len(tb) == 32
            upto = min(k + 1, len(la), len(lb))
            worst = max([worst] + [abs(x - y) for x, y in zip(la[:upto], lb[:upto])])
        if worst > TP_LOGPROB_MARGIN:
            raise PhaseFailed(
                f"tp{wide} vs tp1: chosen-token logprobs differ by "
                f"{worst:.3f} > {TP_LOGPROB_MARGIN}"
            )
        return {
            "platform": runs[1]["device"]["platform"],
            "devices": {f"tp{tp}": r["device"] for tp, r in runs.items()},
            "texts_equal_32_tokens": f"{equal}/5",
            "max_logprob_diff": round(worst, 4),
            "margin": TP_LOGPROB_MARGIN,
            "sample": runs[1]["outs"][0][0],
        }

    def train_sharded_phase(self):
        seq = "64" if self.rehearse else "1024"
        base = ["--full", "--batch", "8", "--seq-len", seq, "--steps", "3", "--dp", "1"]
        runs = {
            name: self.finetune(base + flags, f"train_{name}.log")
            for name, flags in (
                ("one_chip", ["--fsdp", "1", "--tp", "1"]),
                ("fsdp4", ["--fsdp", "4", "--tp", "1"]),
                ("fsdp2_tp2", ["--fsdp", "2", "--tp", "2"]),
            )
        }
        ref = runs["one_chip"]["losses"][0]
        for name, r in runs.items():
            if abs(r["losses"][0] - ref) > SHARDED_LOSS_MARGIN:
                raise PhaseFailed(
                    f"{name}: step-1 loss {r['losses'][0]} vs one chip {ref} "
                    f"(margin {SHARDED_LOSS_MARGIN})"
                )
            in_use = r["device_bytes_in_use"]
            if name != "one_chip" and in_use and not all(in_use):
                raise PhaseFailed(f"{name}: state not spread: {in_use}")
        return {
            "platform": runs["fsdp4"]["device"]["platform"],
            "step1_loss": {n: r["losses"][0] for n, r in runs.items()},
            "margin": SHARDED_LOSS_MARGIN,
            "device_bytes_in_use": {
                n: r["device_bytes_in_use"] for n, r in runs.items()
            },
        }

    def replicas_phase(self):
        """One in-process replica per chip behind the real router."""
        artifact = os.path.join(self.work, "soak.json")
        out = self.run(
            [sys.executable, "-m", "dstack_tpu.loadgen", "--model", self.model,
             "--replicas", str(self.chips), "--no-chaos", "--duration", "30",
             "--rate", "1", "--output", artifact],
            "replicas.log", timeout=1500,
        )
        r = last_json(out)
        placed = sorted(tuple(d) for d in r["replica_devices"].values())
        if len(set(placed)) != self.chips:
            raise PhaseFailed(f"replicas share devices: {r['replica_devices']}")
        if r["client_5xx"] or r["failures"]:
            raise PhaseFailed(f"client_5xx={r['client_5xx']} failures={r['failures']}")
        in_use = r["device_bytes_in_use"]
        if not self.rehearse and not all(b and b > 1e9 for b in in_use):
            raise PhaseFailed(f"a chip holds no replica: bytes_in_use {in_use}")
        return {
            "platform": r["device"]["platform"],
            "replica_devices": r["replica_devices"],
            "device_bytes_in_use": in_use,
            "client_5xx": r["client_5xx"], "failures": r["failures"],
            "events": r["events"], "goodput_ratio": r["value"],
        }


# ---- helpers (stdlib) ---------------------------------------------------


def tail(path, n=3000, skip_after=None):
    try:
        with open(path) as f:
            text = f.read()
    except OSError:
        return ""
    if skip_after and skip_after in text:
        text = text[: text.index(skip_after)]
    return text[-n:]


def last_json(out):
    for line in reversed(out.strip().splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise PhaseFailed("child printed no JSON line:\n" + out[-1500:])


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def port_open(port):
    with socket.socket() as s:
        s.settimeout(1.0)
        return s.connect_ex(("127.0.0.1", port)) == 0


def words(seed, n_chars):
    """Deterministic ASCII filler of exactly ``n_chars`` characters (one
    byte-tokenizer token each)."""
    vocab = ("tensor", "slice", "mesh", "router", "prefill", "decode",
             "cache", "shard", "kernel", "token", "batch", "queue")
    out, i = [], seed
    while sum(len(w) + 1 for w in out) < n_chars + 1:
        i = (i * 1103515245 + 12345) % (1 << 31)
        out.append(vocab[i % len(vocab)] + str(i % 97))
    return " ".join(out)[:n_chars]


def http_json(method, port, path, body=None, timeout=300):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(
            method, path, body=json.dumps(body) if body is not None else None,
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        raw = resp.read()
        try:
            return resp.status, json.loads(raw)
        except ValueError:
            return resp.status, {"raw": raw[:500].decode(errors="replace")}
    finally:
        conn.close()


def expect_tokens(status_body, n):
    status, body = status_body
    if status != 200:
        raise PhaseFailed(f"HTTP {status}: {json.dumps(body)[:600]}")
    got = body["usage"]["completion_tokens"]
    if got != n:
        raise PhaseFailed(
            f"asked for {n} tokens, usage.completion_tokens={got} "
            f"(finish_reason {body['choices'][0].get('finish_reason')})"
        )
    return body


def stream(port, path, body, timeout=600):
    """One SSE chat completion → status, finish reason, in-band error."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    out = {"status": None, "finish": None, "error": None, "done": False}
    try:
        conn.request("POST", path, body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        out["status"] = resp.status
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            if line == "data: [DONE]":
                out["done"] = True
                break
            event = json.loads(line[6:])
            if "error" in event:
                out["error"] = event["error"]
            for choice in event.get("choices", []):
                out["finish"] = choice.get("finish_reason") or out["finish"]
    except OSError as e:
        out["error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
    if not out["done"] and not out["error"]:
        out["error"] = "stream ended without [DONE]"
    return out


def concurrently(calls):
    results = [None] * len(calls)

    def work(i):
        results[i] = calls[i]()

    threads = [threading.Thread(target=work, args=(i,)) for i in range(len(calls))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
    if any(r is None for r in results):
        raise PhaseFailed("a concurrent request did not finish within 900s")
    return results


def metrics(port):
    """``/metrics`` text → {family: sum over its series} plus
    {(family, labels): value}; a family with no sample yet reads 0."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
    finally:
        conn.close()
    m = collections.defaultdict(float)
    for line in text.splitlines():
        match = re.match(r"^(\w+)(\{[^}]*\})? ([-+0-9.eEinfa]+)$", line)
        if match:
            name, labels, value = match.groups()
            m[name] += float(value)
            m[name, labels or ""] = float(value)
    return m


def by_label(m, family, label):
    out = collections.defaultdict(float)
    for key, value in m.items():
        found = isinstance(key, tuple) and re.search(label + r'="([^"]*)"', key[1])
        if found and key[0] == family:
            out[found.group(1)] += value
    return dict(out)


# ---- children: the only code here that touches jax ----------------------


def child_device(rehearse):
    import importlib.metadata

    import jax

    from dstack_tpu.utils.backend import compile_cache_dir, device_info

    if rehearse:
        jax.config.update("jax_platforms", "cpu")
    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({
        **device_info(),
        "jax": jax.__version__,
        "libtpu": importlib.metadata.version("libtpu"),
        "memory_bytes_limit": stats.get("bytes_limit"),
        "compile_cache": compile_cache_dir(),
    }))


def child_numerics(rehearse):
    """Full width, on the chip: (1) the Pallas flash path against the
    XLA reference on the same q/k/v, forward and gradients; (2) the
    compiled programs of the platform-gated dispatch really contain the
    kernel; (3) the engine's first 32 greedy tokens against
    teacher-forced ``llama.forward`` logits."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from dstack_tpu.utils.backend import device_info, enable_compile_cache

    if rehearse:
        jax.config.update("jax_platforms", "cpu")
    enable_compile_cache()

    from dstack_tpu.models import llama
    from dstack_tpu.ops.attention import attention
    from dstack_tpu.ops.flash import flash_attention
    from dstack_tpu.serve.engine import (
        GenParams,
        InferenceEngine,
        prefill_chunk_step,
    )

    device = device_info()
    on_chip = device["platform"] == "tpu"
    config = llama.CONFIGS[REHEARSE_MODEL if rehearse else MODEL]
    # the kernel needs head_dim % 64: off the chip the interpreter runs
    # it at the head_dim-64 tiny widths
    kc = config if on_chip else llama.CONFIGS["llama-tiny-64"]
    bf16 = kc.dtype == jnp.bfloat16
    tol = {
        "fwd_max_abs": 2e-2 if bf16 else 2e-5,
        "grad_rel_to_max": 5e-2 if bf16 else 1e-4,
        "logit_margin": 0.25 if config.dtype == jnp.bfloat16 else 1e-3,
    }
    out = {"platform": device["platform"], "device": device, "tolerances": tol}
    problems = []

    # (1) flash vs xla
    b, t = (2, 1024) if on_chip else (1, 128)
    keys = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(keys[0], (b, kc.n_heads, t, kc.head_dim), kc.dtype)
    k = jax.random.normal(keys[1], (b, kc.n_kv_heads, t, kc.head_dim), kc.dtype)
    v = jax.random.normal(keys[2], (b, kc.n_kv_heads, t, kc.head_dim), kc.dtype)
    w = jax.random.normal(keys[3], q.shape, jnp.float32)
    flash = (
        partial(attention, causal=True, impl="flash") if on_chip
        else partial(flash_attention, causal=True, interpret=True)
    )

    def value_and_grads(fn):
        def loss(q, k, v):
            o = fn(q, k, v)
            return (o.astype(jnp.float32) * w).sum(), o

        (_, o), grads = jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
        )(q, k, v)
        return o.astype(jnp.float32), [g.astype(jnp.float32) for g in grads]

    o_f, g_f = value_and_grads(flash)
    o_x, g_x = value_and_grads(partial(attention, causal=True, impl="xla"))
    fwd_err = float(jnp.max(jnp.abs(o_f - o_x)))
    grad_err = [
        float(jnp.max(jnp.abs(a - r)) / jnp.max(jnp.abs(r)))
        for a, r in zip(g_f, g_x)
    ]
    out["flash_vs_xla"] = {
        "shape_bhtd": list(q.shape), "kv_heads": kc.n_kv_heads,
        "fwd_max_abs_err": fwd_err, "grad_rel_err_dq_dk_dv": grad_err,
        "finite": bool(jnp.isfinite(o_f).all()),
    }
    if not (fwd_err <= tol["fwd_max_abs"] and jnp.isfinite(o_f).all()):
        problems.append("flash forward differs from xla")
    if not all(e <= tol["grad_rel_to_max"] for e in grad_err):
        problems.append("flash gradients differ from xla")

    # (3) engine greedy tokens vs teacher-forced forward (built first:
    # (2) lowers the engine's own prefill step)
    params = llama.init_params(config, jax.random.key(0))
    engine = InferenceEngine(config, params, max_batch=16, max_seq=2048)
    prompt = [(i * 31 + 7) % 251 + 1 for i in range(300)]  # > one chunk
    slot, first = engine.add_request(list(prompt), GenParams(max_new_tokens=32))
    generated = [first]
    while engine.active[slot]:
        generated += engine.step().get(slot, [])
    engine.release(slot)
    seq = jnp.asarray([prompt + generated[:-1]], jnp.int32)
    logits = jax.jit(partial(llama.forward, config=config))(params, seq)[0]
    rows = logits[len(prompt) - 1:]  # row i predicts generated[i]
    top = jnp.max(rows, axis=-1)
    chosen = rows[jnp.arange(len(generated)), jnp.asarray(generated)]
    gaps = [float(x) for x in (top - chosen)]
    out["engine_vs_forward"] = {
        "tokens": len(generated),
        "argmax_matches": sum(g == 0.0 for g in gaps),
        "max_logit_gap": max(gaps),
        "finite": bool(jnp.isfinite(rows).all()),
    }
    if len(generated) != 32 or not jnp.isfinite(rows).all():
        problems.append("engine did not produce 32 finite-logit tokens")
    if max(gaps) > tol["logit_margin"]:
        problems.append("engine token outside the margin of forward's top logit")

    # (2) on tpu the platform gates must resolve to the compiled kernel
    if on_chip:
        auto = jax.jit(partial(attention, causal=True)).lower(q, k, v)
        chunk = jax.jit(
            partial(prefill_chunk_step, config=config, start=0)
        ).lower(
            engine.params, engine.cache, jnp.zeros((1, 256), jnp.int32),
            jnp.asarray(0, jnp.int32), jnp.asarray(255, jnp.int32),
        )
        out["kernel_in_program"] = {
            "attention_auto": "tpu_custom_call" in auto.compile().as_text(),
            "prefill_chunk_step": "tpu_custom_call" in chunk.compile().as_text(),
        }
        if not all(out["kernel_in_program"].values()):
            problems.append("a platform gate resolved away from the kernel")

    out["pass"] = not problems
    out["problems"] = problems
    print(json.dumps(out))


# ---- main ---------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    ap.add_argument("--rehearse", action="store_true",
                    help="llama-tiny on the CPU; never a chip pass")
    ap.add_argument("--only", default=None,
                    help="comma list of phases (debugging; prints no result line)")
    ap.add_argument("--child", choices=["device", "numerics"],
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        {"device": child_device, "numerics": child_numerics}[args.child](
            args.rehearse
        )
        return 0
    if not os.path.isdir(os.path.join(HERE, "dstack_tpu")):
        print("chip_smoke.py: no dstack_tpu/ beside this script — it drives "
              "the repository's entry points and is nothing alone",
              file=sys.stderr)
        return 1

    smoke = Smoke(args.rehearse, args.chips)
    phases = {"device": smoke.device_phase}
    if args.chips == 1:
        phases.update(serve=smoke.serve_phase, numerics=smoke.numerics_phase,
                      train=smoke.train_phase)
    else:
        phases.update(serve_tp=smoke.serve_tp_phase,
                      train_sharded=smoke.train_sharded_phase,
                      replicas=smoke.replicas_phase)
    chosen = args.only.split(",") if args.only else list(phases)
    unknown = [p for p in chosen if p not in phases]
    if unknown:
        ap.error(f"unknown phase(s) {unknown}; have {list(phases)}")
    try:
        for name in ["device"] + [p for p in chosen if p != "device"]:
            smoke.phase(name, phases[name])
    finally:
        smoke.close()
    print(json.dumps(result_line(args.rehearse, args.only, smoke.device)))
    return 0


def result_line(rehearse, only, device):
    """The last line. Only a whole run on the accelerator says ``ok``:
    a rehearsal or a partial run carries no such key, so neither can be
    read as a chip pass."""
    if only:
        return {"partial": only, "rehearse": rehearse, "device": device}
    if rehearse:
        return {"rehearsal": "passed", "device": device}
    return {"ok": True, "device": device}


if __name__ == "__main__":
    sys.exit(main())
