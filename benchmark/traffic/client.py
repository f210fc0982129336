"""The load generator's transport: one process, one event loop.

Sends the generated requests to ``/v1/chat/completions`` with
``stream: true`` and records, by the host's monotonic clock, when each
was due, when it was sent, and when every token-carrying delta arrived
with how many tokens. Requests carry no ``logit_bias`` and no
``logprobs``. Open loop: a request is sent when it is due, whatever the
server is doing. Closed loop: a client sends its next request when its
last one has completed.
"""

import asyncio
import json
import time

import aiohttp

from benchmark import tokenizer


class Record:
    __slots__ = (
        "req", "due", "sent", "deltas", "ids", "done", "error", "finish",
    )

    def __init__(self, req, due):
        self.req, self.due = req, due
        self.sent = None
        self.deltas = []  # (monotonic time, tokens in the delta)
        self.ids = []
        self.done = None  # time of [DONE]
        self.error = None
        self.finish = None

    @property
    def first(self):
        return self.deltas[0][0] if self.deltas else None


def payload(req: dict, model: str) -> dict:
    body = {
        "model": model,
        "messages": [{"role": "user", "content": tokenizer.text_of(req["prompt_ids"])}],
        "max_tokens": req["max_tokens"],
        "temperature": req["temperature"],
        "stream": True,
    }
    if req.get("seed") is not None:
        body["seed"] = req["seed"]
    return body


async def send(session, url: str, model: str, rec: Record) -> Record:
    """One streamed request → its record filled in; never raises."""
    rec.sent = time.monotonic()
    try:
        async with session.post(url, json=payload(rec.req, model)) as resp:
            if resp.status != 200:
                rec.error = f"http {resp.status}: {(await resp.text())[:200]}"
                return rec
            buf = b""
            async for chunk in resp.content.iter_any():
                now = time.monotonic()
                buf += chunk
                while b"\n\n" in buf:
                    event, buf = buf.split(b"\n\n", 1)
                    if not event.startswith(b"data: "):
                        continue
                    data = event[6:]
                    if data == b"[DONE]":
                        rec.done = now
                        continue
                    msg = json.loads(data)
                    if "error" in msg:
                        rec.error = f"stream error: {msg['error']}"
                        continue
                    choice = msg["choices"][0]
                    text = (choice.get("delta") or {}).get("content")
                    if text:
                        ids = tokenizer.ids_of(text)
                        if ids:
                            rec.ids += ids
                            rec.deltas.append((now, len(ids)))
                    if choice.get("finish_reason"):
                        rec.finish = choice["finish_reason"]
    except asyncio.CancelledError:
        raise
    except Exception as e:  # the boundary: a failed request is a count
        rec.error = f"{type(e).__name__}: {e}"
    return rec


async def run_traffic(
    base_url: str, model: str, plan: dict, seconds: float, drain_s: float,
    marks=(),
) -> dict:
    """Drive ramp + window. ``marks`` is a list of ``(seconds after the
    window opens, async function)`` awaited in order at those times
    (metric scrapes at the edges, the profiler). Returns
    ``{"t_open", "t_close", "records"}``; requests still running
    ``drain_s`` after the close are cancelled."""
    url = base_url + "/v1/chat/completions"
    ramp = plan["ramp_s"]
    conn = aiohttp.TCPConnector(limit=0)
    records, tasks = [], []
    async with aiohttp.ClientSession(
        connector=conn, timeout=aiohttp.ClientTimeout(total=None)
    ) as session:
        t_start = time.monotonic()
        t_open = t_start + ramp
        t_close = t_open + seconds

        async def sleep_until(t):
            d = t - time.monotonic()
            if d > 0:
                await asyncio.sleep(d)

        async def open_loop():
            for req in sorted(plan["requests"], key=lambda r: r["due_s"]):
                due = t_open + req["due_s"]
                await sleep_until(due)
                rec = Record(req, due)
                records.append(rec)
                tasks.append(asyncio.ensure_future(send(session, url, model, rec)))

        async def closed_client(c, reqs, start):
            await sleep_until(t_open + start)
            for req in reqs:
                if time.monotonic() >= t_close:
                    return
                rec = Record(req, time.monotonic())
                records.append(rec)
                await send(session, url, model, rec)
                if rec.error:
                    await asyncio.sleep(0.05)  # a refusing server is not hammered

        async def edges():
            for offset, fn in sorted(marks, key=lambda m: m[0]):
                await sleep_until(t_open + offset)
                await fn()

        if plan["loop"] == "open":
            drivers = [asyncio.ensure_future(open_loop())]
        else:
            by_client = {}
            for r in sorted(plan["requests"], key=lambda r: r["order"]):
                by_client.setdefault(r["client"], []).append(r)
            drivers = [
                asyncio.ensure_future(closed_client(c, reqs, plan["client_start_s"][c]))
                for c, reqs in sorted(by_client.items())
            ]
        edge = asyncio.ensure_future(edges())
        await sleep_until(t_close)
        await edge
        await sleep_until(t_close + drain_s)
        for t in drivers + tasks:
            t.cancel()
        await asyncio.gather(*drivers, *tasks, return_exceptions=True)
    return {"t_open": t_open, "t_close": t_close, "records": records}
