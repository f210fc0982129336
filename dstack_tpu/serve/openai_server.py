"""OpenAI-compatible inference server over the slot engine.

``python -m dstack_tpu.serve.openai_server --model llama-3-8b
--weights w.npz --tokenizer /path`` is a runnable ``type: service``
command on any slice the orchestrator provisions: the gateway's model
proxy (format: openai, default prefix /v1) points straight at it.

Endpoints: ``/v1/models``, ``/v1/chat/completions`` (plain + SSE
streaming), ``/v1/completions``, ``/health``. Requests queue into the
continuous-batching engine; one background asyncio task drives
prefills and decode steps for all in-flight requests (the jitted step
runs in a thread so the event loop keeps serving).

Multi-tenant QoS (``dstack_tpu.qos``): per-tenant token buckets shed
over-budget tenants with 429 + ``Retry-After`` before any prompt work;
admission to engine slots is priority-ordered (``X-DTPU-Priority``:
interactive/standard/batch) with per-tenant in-flight caps so one
flooding tenant can never hold every slot. Policy comes from
``DTPU_QOS_*`` env (injected by the job configurator from the service
spec's ``qos`` block) or the ``--qos-*`` flags.

Request-lifecycle hardening (serving.md §9):

- **Per-request deadlines.** ``X-DTPU-Deadline`` (seconds) — or
  ``DTPU_REQUEST_DEADLINE_DEFAULT`` when absent — arms a
  ``utils/retry.Deadline`` that follows the request from the pending
  queue into its engine slot; the scheduler aborts expired requests
  every tick (slot released → KV freed, 504 to the client, un-started
  QoS token refunded). The ``serve.deadline`` fault point injects
  clock skew into the check.
- **Engine watchdog.** ``DTPU_ENGINE_WATCHDOG_SECONDS`` bounds one
  ``engine.step`` dispatch: a wedged step (the ``serve.engine.step``
  hang fault, or a stuck device) is abandoned and only the wedged slot
  is aborted — the other in-flight streams keep decoding.
- **Resumable continuations.** The router's mid-stream failover
  re-dispatches a dying stream here with ``dtpu_resume`` + the
  proxy-asserted ``X-DTPU-Resume`` header: the delivered text is
  appended to the rendered prompt (re-prefill rides the prefix cache),
  the budget shrinks accordingly, seeded streams replay their PRNG
  advance, and the continuation is neither re-charged nor re-shed.
"""

import argparse
import asyncio
import contextlib
import contextvars
import json
import os
import re
import time
import uuid
from pathlib import Path
from typing import Optional

from aiohttp import web

from dstack_tpu import faults, qos
from dstack_tpu.obs import boot as obs_boot
from dstack_tpu.obs import flight
from dstack_tpu.obs import profiling as obs_profiling
from dstack_tpu.obs import slo as obs_slo
from dstack_tpu.obs import tracing
from dstack_tpu.obs.tracing import get_trace_registry
from dstack_tpu.proxy.model_tgi import DEFAULT_CHAT_TEMPLATE, render_chat
from dstack_tpu.qos.metrics import get_qos_registry
from dstack_tpu.serve.engine import GenParams, InferenceEngine
from dstack_tpu.serve.tokenizer import Tokenizer, load_tokenizer
from dstack_tpu.utils.backend import (
    device_info,
    enable_compile_cache,
    select_platform,
)
from dstack_tpu.utils.logging import get_logger
from dstack_tpu.utils.retry import Deadline

logger = get_logger("serve.openai")

# build_app boot param sentinel: "use the process-global recorder" —
# distinct from an explicit None ("this app has no boot recorder")
_BOOT_FROM_ENV = object()


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.getenv(name, "") or default)
    except ValueError:
        return default


class _Request:
    def __init__(
        self,
        prompt_ids: list[int],
        gen: GenParams,
        tenant: str = qos.ANONYMOUS_TENANT,
        priority: int = qos.PRIORITY_STANDARD,
    ):
        self.prompt_ids = prompt_ids
        self.gen = gen
        self.tenant = tenant
        self.priority = priority
        self.cap_deferred = False  # counted once in inflight_deferred_total
        self.submitted_at: Optional[float] = None  # set by Scheduler.submit
        self.queue: asyncio.Queue = asyncio.Queue()  # token ids, then None
        self.error: Optional[str] = None
        self.error_status = 500  # HTTP status a non-streaming error maps to
        self.retry_after: Optional[int] = None  # hint for 429/503 errors
        self.finish_reason: Optional[str] = None
        self.cancelled = False
        self.gen_ids: list[int] = []  # for stop-string matching
        # per generated token: (logprob, [(alt_id, alt_lp), ...])
        self.logprob_entries: list = []
        # lifecycle hardening (serving.md §9)
        self.deadline: Optional[Deadline] = None
        self.bucket = None  # qos.TokenBucket this request's admission charged
        self.refunded = False
        self.started = False  # at least one token queued to the client
        # when the scheduler handed over the oldest token the stream
        # handler has not yet answered with a delta (None: none waits)
        self.handed_at: Optional[float] = None
        # distributed tracing: `span` is the request's serve-side root
        # (parented to the router's dispatch leg via X-DTPU-Trace);
        # `phase` is the currently-open engine phase child —
        # serve.queue → serve.prefill → serve.decode — advanced by the
        # scheduler. Both default to the shared no-op span.
        self.span = tracing.NOOP_SPAN
        self.phase = tracing.NOOP_SPAN


def _reap_abandoned_step(task) -> None:
    """Done-callback for a watchdog-abandoned engine step: its outcome
    is deliberately discarded (the engine's epoch guard already made it
    a no-op) — retrieving the exception just keeps asyncio from logging
    'exception was never retrieved'."""
    if task.cancelled():
        return
    exc = task.exception()
    if exc is not None:
        logger.warning("abandoned engine step finally returned: %r", exc)


class Scheduler:
    """Bridges HTTP handlers and the synchronous engine: a background
    task prefills pending requests into free slots and steps the engine
    while anything is active.

    Admission is priority-aware, not FIFO: pending requests pop by
    (priority class, arrival order) and a per-tenant in-flight cap
    (``tenant_inflight``) skips — but keeps queued — requests whose
    tenant already holds its share of slots, so interactive traffic is
    admitted ahead of batch and no tenant can occupy every slot."""

    def __init__(
        self,
        engine: InferenceEngine,
        tokenizer: Tokenizer,
        tenant_inflight: int = 0,
        watchdog_seconds: float = 0.0,
        boot=None,
    ):
        self.engine = engine
        self.tokenizer = tokenizer
        self.pending = qos.PriorityPending()
        self.tenant_inflight = max(0, int(tenant_inflight))  # 0 = off
        # boot recorder (obs/boot.py): the scheduler owns the
        # first-served-token milestone — the instant the FIRST token of
        # this process's lifetime is queued to a client, TTFST is over.
        # A local bool guards the hot path so steady state pays one
        # attribute read, not a recorder call per token.
        self._boot = boot
        self._boot_served = boot is None
        # engine watchdog: one step() dispatch may take at most this
        # long before it is abandoned and the wedged slot aborted
        # (0 = off — DTPU_ENGINE_WATCHDOG_SECONDS via build_app)
        self.watchdog_seconds = max(0.0, float(watchdog_seconds))
        # a dispatch-abandoned step still OWNS the engine until its
        # thread returns: while set, ticks neither admit nor dispatch
        self._abandoned: Optional[asyncio.Task] = None
        self.by_slot: dict[int, _Request] = {}
        self.by_prefill: dict[int, _Request] = {}  # chunked prefills in flight
        self._task: Optional[asyncio.Task] = None
        # host-phase accounting (PERF.md §3): when the last engine call
        # returned (None while parked idle: a park is not a gap), and
        # this tick's own host seconds so far
        self._engine_returned: Optional[float] = None
        self._tick_host_s = 0.0
        # the gap's named parts noted since the last engine call
        # returned, as (family, seconds): observed when the next call
        # counts its gap, dropped by a park
        self._gap_parts: list = []
        # when the last token hand-over returned: what the loop gives
        # away from there to the next tick's first line, with no engine
        # call in flight, is dtpu_serve_loop_yield_seconds
        self._handed_over_at: Optional[float] = None
        # engine calls in flight (one; two while a watchdog-abandoned
        # step's thread is still out). The stream handlers read it once
        # a token: their turn lies inside a call's await, while the
        # device computes, and the overlapped-token counter says so
        self.calls_in_flight = 0
        # serving metrics live in the ENGINE's obs registry (one source
        # of truth); /metrics renders the registry for the shim relay →
        # server prometheus plane and for the benchmark's readers.

    def start(self) -> None:
        self._task = asyncio.create_task(self._loop())

    def _note_served_token(self) -> None:
        """First token of the process's lifetime queued to a client →
        the boot recorder's terminal milestone (seals the boot trace,
        observes TTFST). `_boot_served` starts True when no recorder
        is attached, so steady state costs one bool check."""
        if not self._boot_served:
            self._boot_served = True
            self._boot.mark(obs_boot.SERVED_MARK)

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()

    async def submit(self, req: _Request) -> None:
        req.submitted_at = time.perf_counter()
        self.engine.metrics.family("dtpu_serve_requests_total").inc(1)
        # first engine phase: time parked in the admission queue (the
        # QoS saturation component of client-observed TTFT)
        req.phase = tracing.span("serve.queue", parent=req.span)
        self.pending.push(req, req.priority)

    def cancel(self, req: _Request) -> None:
        """Client went away: mark the request, and the next tick's sweep
        (``_admit_pending``) frees its slot so decode stops burning
        steps on an abandoned generation (or its remaining prefill
        chunks). The engine is NOT touched here: a handler runs while
        an engine call is in flight on the worker thread, and a
        ``release`` under a running step is overwritten by the device
        mirror the step restores (the released slot would go on writing
        its cache rows). A request cancelled before its first token
        refunds its QoS charge (the satellite invariant:
        abusive-reconnect churn must not burn a victim tenant's
        budget)."""
        req.cancelled = True
        self._refund_unstarted(req)
        req.phase.end("cancelled")

    def _count_error(self, req: _Request) -> None:
        """One server-side request failure (engine/prefill/admission
        error, watchdog abort, deadline expiry) into
        ``dtpu_serve_request_errors_total`` — the live SLO engine's
        error-rate signal. Honest overload sheds (the wedge-quiesce
        503, which carries Retry-After per DTPU007) are not failures
        and are not counted."""
        self.engine.metrics.family(
            "dtpu_serve_request_errors_total"
        ).inc(1)

    def _refund_unstarted(self, req: _Request) -> None:
        """Return the admission charge of a request that dies before
        delivering its first token (disconnect, deadline expiry,
        watchdog abort, engine failure). A completed — or even merely
        started — generation keeps its charge; the refund is
        idempotent per request."""
        if (
            req.bucket is not None
            and not req.refunded
            and not req.started
            and req.finish_reason is None
        ):
            req.refunded = True
            req.bucket.refund(1.0)

    # ---- per-request deadlines ----

    def _deadline_expired(self, req: _Request) -> bool:
        """One deadline check; the ``serve.deadline`` fault point's
        mutate value is added as clock skew so chaos plans can force
        expiry deterministically."""
        if req.deadline is None or req.cancelled:
            return False
        skew = faults.mutate("serve.deadline", 0.0)
        try:
            skew = float(skew)
        except (TypeError, ValueError):
            skew = 0.0
        rem = req.deadline.remaining()
        return rem is not None and rem - skew <= 0.0

    def _abort_expired(self) -> None:
        """Deadline sweep, once per scheduler tick: expired slots are
        aborted (KV freed immediately — the slot re-enters the free
        pool this tick) and expired queued requests fail loudly
        instead of rotting in the heap; un-started charges refund."""
        for table in (self.by_slot, self.by_prefill):
            expired = [
                (slot, req)
                for slot, req in list(table.items())
                if self._deadline_expired(req)
            ]
            for slot, req in expired:
                del table[slot]
                self.engine.release(slot)
                self._fail_deadline(req)
            if expired and flight.enabled():
                # deadline batch-abort: the post-mortem names the
                # aborted slots and their traces so a deadline storm
                # is attributable after the fact
                flight.post_mortem(
                    "deadline_abort",
                    registry=self.engine.metrics,
                    slots={
                        slot: (
                            req.span.trace_id if req.span.recording
                            else None
                        )
                        for slot, req in expired
                    },
                    **self.engine.fault_ctx,
                )
        if self.pending.qsize():
            for req in self.pending.drain_matching(self._deadline_expired):
                self._fail_deadline(req)

    def _fail_deadline(self, req: _Request) -> None:
        self.engine.metrics.family(
            "dtpu_serve_deadline_expired_total"
        ).inc(1)
        self._count_error(req)
        self._refund_unstarted(req)
        # terminating trace event: the deadline sweep, not the engine,
        # ended this request — a trace of the 504 says so explicitly
        req.span.event("deadline_expired")
        req.phase.end("deadline")
        req.error = "request deadline exceeded"
        req.error_status = 504
        req.queue.put_nowait(None)

    # ---- host-phase accounting ----

    def _engine_call(self, fn):
        """Hand ``fn`` (``engine.step`` / ``engine.prefill_wave``) to a
        worker thread NOW — a plain function, so the call is out before
        the caller's ``await`` (or the watchdog's task) runs a line —
        and return the awaitable of its result: whoever the loop runs at
        that ``await`` (the stream handlers, woken by the last
        hand-over) then runs beside the call, not before it.
        The time since the previous engine call returned — the device
        had no work queued while requests held slots — goes to
        ``dtpu_serve_host_gap_seconds``; both clock reads are taken on
        the worker thread, so the gap includes the thread hops, and
        this is where each hop is read from both sides:
        ``dtpu_serve_worker_start_seconds`` on the way out,
        ``dtpu_serve_loop_return_seconds`` on the way back (noted, and
        observed with the gap's other parts by the call that counts
        the gap)."""
        family = self.engine.metrics.family
        prev = self._engine_returned
        for name, seconds in self._gap_parts:  # of the gap this call ends
            family(name).observe(seconds)
        self._gap_parts.clear()
        t_hop = time.perf_counter()

        def run():
            t0 = time.perf_counter()
            if prev is not None:
                family("dtpu_serve_host_gap_seconds").observe(t0 - prev)
                family("dtpu_serve_worker_start_seconds").observe(t0 - t_hop)
            try:
                return fn()
            finally:
                self._engine_returned = time.perf_counter()

        self.calls_in_flight += 1
        return self._call_done(asyncio.get_running_loop().run_in_executor(
            None, contextvars.copy_context().run, run
        ))

    async def _call_done(self, call: asyncio.Future):
        """The result of a started engine call. Its ``await`` is where
        the loop is given away, with the call on its way to the device:
        the stream handlers take their turn here."""
        try:
            out = await call
        finally:
            self.calls_in_flight -= 1
        self._gap_parts.append((
            "dtpu_serve_loop_return_seconds",
            time.perf_counter() - self._engine_returned,
        ))
        return out

    @contextlib.contextmanager
    def _host_code(self):
        """The tick's OWN code (sweep, admission, token hand-over — not
        the engine calls): span ``dtpu.tick.host`` in a capture, summed
        into one ``dtpu_serve_tick_host_seconds`` observation a tick."""
        t0 = time.perf_counter()
        try:
            with obs_profiling.span("dtpu.tick.host"):
                yield
        finally:
            self._tick_host_s += time.perf_counter() - t0

    # ---- engine watchdog ----

    async def _guarded_step(self) -> Optional[dict]:
        """``engine.step`` on a worker thread, under the watchdog: a
        dispatch exceeding ``watchdog_seconds`` is abandoned (the
        engine's step-epoch guard neutralizes the stuck thread's
        eventual return) and the wedged slot — or, when the wedge is
        inside the jitted dispatch and unattributable, the whole batch
        — is aborted, so one stuck dispatch cannot freeze every
        stream. Returns None when the watchdog tripped (this tick
        produced no tokens); engine errors propagate as before."""
        if self.watchdog_seconds <= 0:
            return await self._engine_call(self.engine.step)
        # the call is started here, not by the task: a task's first line
        # runs a loop iteration later, behind the handlers the hand-over
        # woke
        task = asyncio.ensure_future(self._engine_call(self.engine.step))
        done, _ = await asyncio.wait({task}, timeout=self.watchdog_seconds)
        if done:
            return task.result()
        phase = self.engine.abandon_step()
        if phase is None:
            # the step finished concurrently with the trip (its wedge
            # marker already cleared): this is a slow step, not a
            # wedge — harvest the result instead of aborting a batch
            # that just decoded successfully
            done, _ = await asyncio.wait(
                {task}, timeout=max(1.0, self.watchdog_seconds)
            )
            if done:
                return task.result()
            # marker cleared but the thread still won't return —
            # treat as an unattributable wedge below
        self.engine.metrics.family("dtpu_serve_watchdog_aborts_total").inc(1)
        task.add_done_callback(_reap_abandoned_step)
        if phase is not None and phase[0] == "slot":
            slot = phase[1]
            req = self.by_slot.pop(slot, None) or self.by_prefill.pop(
                slot, None
            )
            self.engine.release(slot)
            logger.error(
                "engine watchdog: step wedged on slot %d for > %.1fs; "
                "aborted that slot, %d other requests keep serving",
                slot, self.watchdog_seconds,
                len(self.by_slot) + len(self.by_prefill),
            )
            if req is not None:
                self._count_error(req)
                self._refund_unstarted(req)
                req.span.event("watchdog_abort", slot=slot)
                req.phase.end("error")
                req.error = "engine watchdog aborted a wedged decode step"
                req.queue.put_nowait(None)
            return None
        # wedged inside the jitted dispatch: no single slot to blame —
        # fail the batch honestly (behind the router these streams
        # resume on another replica) rather than freezing every stream.
        # The stuck thread still owns the engine's buffers: quiesce
        # (no admission, no new dispatch) until it actually returns.
        logger.error(
            "engine watchdog: dispatch wedged for > %.1fs with no "
            "attributable slot; failing all %d in-flight requests and "
            "quiescing until the stuck dispatch returns",
            self.watchdog_seconds,
            len(self.by_slot) + len(self.by_prefill),
        )
        for table in (self.by_slot, self.by_prefill):
            for slot, req in list(table.items()):
                self.engine.release(slot)
                self._count_error(req)
                self._refund_unstarted(req)
                req.span.event("watchdog_abort", attributable=False)
                req.phase.end("error")
                req.error = "engine watchdog aborted a wedged decode step"
                req.queue.put_nowait(None)
            table.clear()
        self._abandoned = task
        return None

    def _tenant_held_counts(self) -> dict:
        """tenant → slots currently held (prefilling or decoding);
        computed ONCE per tick and updated as admissions are granted —
        a per-candidate rescan would be O(pending × inflight)."""
        counts: dict = {}
        for r in self.by_slot.values():
            counts[r.tenant] = counts.get(r.tenant, 0) + 1
        for r in self.by_prefill.values():
            counts[r.tenant] = counts.get(r.tenant, 0) + 1
        return counts

    def _tenant_cap_ok(self, req: _Request, counts: dict) -> bool:
        """Admission predicate against the tick's held-count snapshot.
        The deferred counter ticks once per REQUEST (first time it
        waits at the cap), not once per scheduler pass."""
        if self.tenant_inflight <= 0:
            return True
        if counts.get(req.tenant, 0) < self.tenant_inflight:
            return True
        if not req.cap_deferred:
            req.cap_deferred = True
            get_qos_registry().family(
                "dtpu_qos_inflight_deferred_total"
            ).inc(1, req.tenant)
        return False

    async def _loop(self) -> None:
        # the loop must survive ANY engine error (bad request shapes,
        # XLA OOM): fail the affected request(s) and keep serving
        while True:
            try:
                await self._tick()
            except asyncio.CancelledError:
                raise
            except Exception as e:  # noqa: BLE001 - reported per request
                logger.exception("scheduler tick failed: %s", e)
                flight.post_mortem(
                    "engine_error",
                    registry=self.engine.metrics,
                    error=str(e)[:200],
                    slots=sorted(self.by_slot),
                    **self.engine.fault_ctx,
                )
                for slot, req in list(self.by_slot.items()):
                    self.engine.release(slot)
                    self._count_error(req)
                    self._refund_unstarted(req)
                    req.phase.end("error")
                    req.error = str(e)
                    req.queue.put_nowait(None)
                self.by_slot.clear()
            if self._tick_host_s:
                self.engine.metrics.family(
                    "dtpu_serve_tick_host_seconds"
                ).observe(self._tick_host_s)
                self._tick_host_s = 0.0

    def _handle_first_token(
        self, slot: int, req: _Request, first: int, now: float
    ) -> bool:
        """Deliver a finished prefill's first token (handed over at
        ``now``, the wave's one clock read); True when the slot stays
        active for the decode loop."""
        req.phase.end()  # serve.prefill: slot admission → first token
        req.phase = tracing.NOOP_SPAN
        if req.gen.logprobs is not None:
            entry = self.engine.take_logprobs(slot)
            if entry is not None:
                req.logprob_entries.append(entry)
        if first != req.gen.eos_id:
            req.started = True  # charge is earned once a token ships
            self._note_served_token()
            if req.handed_at is None:
                req.handed_at = now
            req.queue.put_nowait(first)
            if self._hit_stop(req, first):
                self.engine.release(slot)
                req.finish_reason = "stop"
                req.queue.put_nowait(None)
                return False
        if self.engine.active[slot]:
            # decode phase: first token → finish, with macro-step
            # events aggregated per engine dispatch (bounded per span)
            req.phase = tracing.span("serve.decode", parent=req.span, slot=slot)
            return True
        req.finish_reason = self.engine.finish_reason[slot]
        req.queue.put_nowait(None)  # finished at first token
        return False

    def _hit_stop(self, req: _Request, tok: int) -> bool:
        """Track generated ids; True once a stop string appears in the
        decoded text. Streaming clients may have already received tokens
        that form the stop string's head — generation halts as soon as
        the match is visible; non-streaming handlers truncate the text.

        Only a bounded tail is decoded per token — full-text rescans
        would be O(n²) over the generation. A char can span up to 4
        tokens (byte-level tokenizers emit one token per UTF-8 byte),
        so the window is 4× the longest stop string plus slack."""
        req.gen_ids.append(tok)
        if not req.gen.stop:
            return False
        keep = 4 * max(len(t) for t in req.gen.stop) + 8
        text = self.tokenizer.decode(req.gen_ids[-keep:])
        return any(t in text for t in req.gen.stop)

    async def _tick(self) -> None:
        if self._handed_over_at is not None:
            self._gap_parts.append((
                "dtpu_serve_loop_yield_seconds",
                time.perf_counter() - self._handed_over_at,
            ))
            self._handed_over_at = None
        if self._abandoned is not None:
            if not self._abandoned.done():
                # a dispatch-abandoned step's thread still owns the
                # engine: fail new arrivals fast (clients must not
                # hang behind a wedge) and wait for it to return
                for req in self.pending.drain_matching(lambda r: True):
                    self._refund_unstarted(req)
                    req.span.event("engine_wedged")
                    req.phase.end("error")
                    req.error = (
                        "engine wedged: a decode dispatch exceeded the "
                        "watchdog budget"
                    )
                    req.error_status = 503
                    # the DTPU007 contract: every 429/503 carries a
                    # retry hint — a wedge clears when the stuck
                    # dispatch returns, so hint one watchdog budget
                    req.retry_after = max(1, int(round(self.watchdog_seconds)))
                    req.queue.put_nowait(None)
                await asyncio.sleep(0.05)
                return
            self._abandoned = None
            # the stale step rebuilt device mirrors from released slot
            # state — drop them before the next dispatch
            self.engine.finish_abandoned_step()
        with self._host_code():
            self._admit_pending()
        # ONE prefill dispatch per tick — a packed wave advancing up to
        # prefill_pack pending prompts a chunk each (engine.prefill_wave)
        # — so decode steps for running slots interleave between chunk
        # waves instead of stalling behind N serial per-prompt prefills
        if self.by_prefill:
            try:
                firsts = await self._engine_call(self.engine.prefill_wave)
            except Exception as e:  # noqa: BLE001 - reported per request
                logger.exception("prefill failed: %s", e)
                flight.post_mortem(
                    "prefill_error",
                    registry=self.engine.metrics,
                    error=str(e)[:200],
                    slots=list(self.engine.last_wave_slots),
                    **self.engine.fault_ctx,
                )
                # fail exactly the rows that were in the failing
                # dispatch (the engine publishes them before running);
                # prompts beyond prefill_pack never ran and keep their
                # place in the queue
                for slot in self.engine.last_wave_slots:
                    req = self.by_prefill.pop(slot, None)
                    if req is None:
                        continue
                    self.engine.release(slot)
                    self._count_error(req)
                    self._refund_unstarted(req)
                    req.phase.end("error")
                    req.error = str(e)
                    req.queue.put_nowait(None)
                return
            with self._host_code():
                now = time.perf_counter()
                for slot, first in firsts.items():
                    # prompt complete; first token sampled
                    req = self.by_prefill.pop(slot, None)
                    if req is None or req.cancelled:
                        # cancel() landed while the wave ran on the
                        # worker thread (the handlers' turn)
                        self.engine.release(slot)
                    elif self._handle_first_token(slot, req, first, now):
                        self.by_slot[slot] = req
        if not self.by_slot:
            if self.by_prefill:
                return  # keep chunking without blocking
            # idle: wait for work instead of spinning. With nothing in
            # flight the tenant caps cannot defer anyone, so an empty
            # by_slot/by_prefill here implies an empty queue — wait()
            # parks until the next push (and a park is not a host gap).
            self._engine_returned = None
            self._gap_parts.clear()
            await self.pending.wait()
            return
        out = await self._guarded_step()
        if out is None:
            return  # watchdog tripped: bookkeeping already done
        with self._host_code():
            self._hand_over(out)
        # NO yield here: the tokens are on the requests' queues, and the
        # next tick's admission and engine call need nothing from the
        # handlers. Their turn (detokenize + SSE write, ~0.2 ms a token)
        # lies inside that call's await, while the device computes
        self._handed_over_at = time.perf_counter()

    def _admit_pending(self) -> None:
        """The tick's admission half, host bookkeeping only."""
        # deadline sweep FIRST: an expired slot frees its KV before the
        # admission pass below, so the reclaimed slot serves live work
        # in the same tick
        self._abort_expired()
        # so does the slot of a request cancelled while the last engine
        # call ran (cancel() only marks it)
        for table in (self.by_slot, self.by_prefill):
            for slot in [s for s, r in table.items() if r.cancelled]:
                self.engine.release(slot)
                del table[slot]
        # admit pending requests into the free slots (host bookkeeping
        # only — the prompt prefills chunk by chunk below) in ONE heap
        # walk: priority-ordered, a tenant at its in-flight cap skipped
        # (stays queued) so other tenants' requests take the slots. The
        # accepting predicate charges `held` so a tenant cannot grab
        # every slot of the batch (pop_admissible_many judges later
        # entries in the same walk).
        held = self._tenant_held_counts()

        def _cap_and_charge(r: _Request) -> bool:
            if not self._tenant_cap_ok(r, held):
                return False
            held[r.tenant] = held.get(r.tenant, 0) + 1
            return True

        free = len(self.engine.free_slots())
        admitted = (
            self.pending.pop_admissible_many(
                free, _cap_and_charge, discard=lambda r: r.cancelled
            )
            if free
            else []
        )
        # adaptive-turbo hint AFTER admission: only work that could
        # still take a slot (not cap-blocked, not cancelled) counts as
        # arrival pressure — a cap-blocked flood's parked backlog must
        # not shrink the macro-step and tax every OTHER tenant's decode
        # throughput (engine._adaptive_turbo_cap)
        self.engine.waiting_requests = int(
            self.pending.any_admissible(
                lambda r: self._tenant_cap_ok(r, held),
                discard=lambda r: r.cancelled,
            )
        )
        for req in admitted:
            try:
                slot = self.engine.start_request(req.prompt_ids, req.gen)
            except Exception as e:  # noqa: BLE001 - reported per request
                logger.exception("admission failed: %s", e)
                self._count_error(req)
                self._refund_unstarted(req)
                req.phase.end("error")
                req.error = str(e)
                req.queue.put_nowait(None)
                # the walk charged `held` for this request; it holds no
                # slot, but the one-tick overcount only defers a same-
                # tenant sibling to the next tick (rare error path)
                continue
            if req.submitted_at is not None:
                # the saturation half of client-observed TTFT: the
                # engine's dtpu_serve_ttft_seconds starts HERE
                wait = time.perf_counter() - req.submitted_at
                self.engine.metrics.family(
                    "dtpu_serve_queue_wait_seconds"
                ).observe(wait)
                prio_label = qos.priority_class_name(req.priority)  # bounded enum
                get_qos_registry().family(
                    "dtpu_qos_queue_wait_seconds"
                ).observe(wait, prio_label)
            # queue phase over: the prefill phase (chunked/packed
            # prefill waves through first token) starts at slot grant
            req.phase.end()
            req.phase = tracing.span(
                "serve.prefill", parent=req.span,
                slot=slot, prompt_tokens=len(req.prompt_ids),
            )
            self.by_prefill[slot] = req

    def _hand_over(self, out: dict) -> None:
        """One engine step's tokens → their requests' queues."""
        now = time.perf_counter()  # ONE read: the call's slots share it
        for slot, toks in out.items():
            req = self.by_slot.get(slot)
            if req is None or req.cancelled:  # the next sweep frees it
                continue
            # one event per engine dispatch: a turbo macro-step or
            # speculative verify counts once with its token yield, so
            # the decode span shows batching granularity, not per-token
            # noise (bounded per span; overflow is counted)
            req.phase.event("macro_step", tokens=len(toks))
            stopped = False
            for tok in toks:  # speculative steps emit several tokens
                if tok == req.gen.eos_id:
                    continue
                if req.gen.logprobs is not None:
                    entry = self.engine.take_logprobs(slot)
                    if entry is not None:
                        req.logprob_entries.append(entry)
                req.started = True
                self._note_served_token()
                if req.handed_at is None:
                    req.handed_at = now
                req.queue.put_nowait(tok)
                if self._hit_stop(req, tok):
                    self.engine.release(slot)
                    req.finish_reason = "stop"
                    req.queue.put_nowait(None)
                    del self.by_slot[slot]
                    stopped = True
                    break
            if stopped:
                req.phase.end(tokens=len(req.gen_ids), finish="stop")
                continue
            if not self.engine.active[slot]:
                req.finish_reason = self.engine.finish_reason[slot]
                req.phase.end(
                    tokens=len(req.gen_ids), finish=req.finish_reason,
                )
                req.queue.put_nowait(None)
                del self.by_slot[slot]


def _truncate_stop(text: str, stop) -> str:
    """Cut the completion at the first stop-string occurrence."""
    if not stop:
        return text
    cut = len(text)
    for t in stop:
        i = text.find(t)
        if i != -1:
            cut = min(cut, i)
    return text[:cut]


def _stop_holdback(text: str, stop) -> int:
    """Chars to withhold from streaming: the longest trailing substring
    of ``text`` that is a proper prefix of some stop string (it may
    complete into the stop sequence on the next token — OpenAI streams
    never deliver any part of a stop sequence)."""
    if not stop:
        return 0
    hold = 0
    for t in stop:
        for p in range(min(len(t) - 1, len(text)), 0, -1):
            if text.endswith(t[:p]):
                hold = max(hold, p)
                break
    return hold


def _logprobs_requested(payload: dict) -> Optional[int]:
    """→ top-n alternatives wanted, or None when logprobs are off.
    0 is valid (chosen-token logprobs, no alternatives). Accepts both
    the completions convention (logprobs: int) and the chat convention
    (logprobs: bool + top_logprobs: int), capped at the engine's
    static TOP_LOGPROBS."""
    from dstack_tpu.serve.engine import TOP_LOGPROBS

    lp = payload.get("logprobs")
    if lp is True:
        n = int(payload.get("top_logprobs") or 0)
        return min(max(n, 0), TOP_LOGPROBS)
    if isinstance(lp, int) and not isinstance(lp, bool) and lp >= 0:
        return min(lp, TOP_LOGPROBS)
    return None


def _kept_token_count(tokenizer: Tokenizer, ids: list, text: str) -> int:
    """Smallest token count whose decoded prefix covers ``text`` — so
    logprobs arrays align with a stop-truncated completion (OpenAI
    truncates text and logprobs consistently).

    Coverage is measured as the common prefix with the FULL decode:
    replacement chars from a partially-decoded multi-byte character
    differ from the final text and don't count, while a genuine U+FFFD
    (invalid bytes the model actually emitted) matches and does."""
    full = tokenizer.decode(ids)
    if len(full) <= len(text):
        return len(ids)
    for k in range(len(ids) + 1):
        prefix = tokenizer.decode(ids[:k])
        common = 0
        for a, b in zip(prefix, full):
            if a != b:
                break
            common += 1
        if common >= len(text):
            return k
    return len(ids)


def _format_completions_logprobs(
    req, tokenizer: Tokenizer, top_n: int, text: str
) -> dict:
    """Legacy /v1/completions logprobs block (4 parallel arrays)."""
    n = _kept_token_count(tokenizer, req.gen_ids, text)
    tokens, token_lps, tops, offsets = [], [], [], []
    pos = 0
    for tok, (lp, alts) in list(zip(req.gen_ids, req.logprob_entries))[:n]:
        piece = tokenizer.decode([tok])
        tokens.append(piece)
        token_lps.append(lp)
        offsets.append(pos)
        pos += len(piece)
        top: dict = {}
        for i, alp in alts[:top_n]:
            # distinct ids can decode to the same text — keep the best
            # (alts arrive sorted descending)
            top.setdefault(tokenizer.decode([i]), alp)
        tops.append(top)
    return {
        "tokens": tokens,
        "token_logprobs": token_lps,
        "top_logprobs": tops,
        "text_offset": offsets,
    }


def _chat_logprob_entries(req, tokenizer: Tokenizer, top_n: int, lo: int, hi: int) -> list:
    """Chat-format content entries for generated tokens [lo, hi)."""
    pairs = list(zip(req.gen_ids, req.logprob_entries))[lo:hi]
    return [
        {
            "token": tokenizer.decode([tok]),
            "logprob": lp,
            "top_logprobs": [
                {"token": tokenizer.decode([i]), "logprob": alp}
                for i, alp in alts[:top_n]
            ],
        }
        for tok, (lp, alts) in pairs
    ]


def _format_chat_logprobs(
    req, tokenizer: Tokenizer, top_n: int, text: str
) -> dict:
    """Chat completions logprobs block, aligned with the final text."""
    n = _kept_token_count(tokenizer, req.gen_ids, text)
    return {"content": _chat_logprob_entries(req, tokenizer, top_n, 0, n)}


def _gen_params(payload: dict, tokenizer: Tokenizer) -> GenParams:
    stop = payload.get("stop")
    if isinstance(stop, str):
        stop = [stop]
    elif not (
        isinstance(stop, list) and all(isinstance(s, str) for s in stop)
    ):
        stop = None
    if stop:  # an empty string would match every completion immediately
        stop = [s for s in stop if s]
    seed = payload.get("seed")
    return GenParams(
        max_new_tokens=int(payload.get("max_tokens") or 256),
        temperature=float(payload.get("temperature") or 0.0),
        top_p=float(payload.get("top_p") or 1.0),
        top_k=int(payload.get("top_k") or 0),
        repetition_penalty=float(payload.get("repetition_penalty") or 1.0),
        presence_penalty=float(payload.get("presence_penalty") or 0.0),
        frequency_penalty=float(payload.get("frequency_penalty") or 0.0),
        min_p=float(payload.get("min_p") or 0.0),
        logit_bias=(
            {int(k): max(-100.0, min(100.0, float(v)))
             for k, v in payload["logit_bias"].items()}
            if isinstance(payload.get("logit_bias"), dict)
            and payload["logit_bias"] else None
        ),
        seed=int(seed) if seed is not None else None,
        eos_id=tokenizer.eos_id,
        stop=stop or None,
        logprobs=_logprobs_requested(payload),
    )


def _bad_sampling_params(payload: dict) -> Optional[str]:
    """Validate the sampling knobs that can't be silently coerced →
    error string for a 400, or None. Runs BEFORE prefill so a malformed
    request can't waste a full prompt's compute."""
    mp = payload.get("min_p")
    if mp is not None:
        try:
            mp = float(mp)
        except (TypeError, ValueError):
            return "'min_p' must be a number"
        if not 0.0 <= mp <= 1.0:
            return "'min_p' must be in [0, 1]"
    lb = payload.get("logit_bias")
    if lb is not None:
        if not isinstance(lb, dict):
            return "'logit_bias' must be an object of {token_id: bias}"
        for k, v in lb.items():
            try:
                int(k)
                float(v)
            except (TypeError, ValueError):
                return f"'logit_bias' entry {k!r} is not numeric"
    return None


def _valid_chat_message(m) -> bool:
    """OpenAI chat message shapes: plain {role, content:str}, assistant
    tool-call messages (content may be null), and role=tool results."""
    if not isinstance(m, dict):
        return False
    if isinstance(m.get("content"), str):
        return True
    return m.get("role") == "assistant" and isinstance(
        m.get("tool_calls"), list
    )


_TOOL_CALL_RE = re.compile(r"<tool_call>\s*(\{.*?\})\s*</tool_call>", re.S)


def _tool_stream_safe_len(out: str) -> int:
    """How much of the accumulated stream text is PROVABLY not part of a
    tool call and may stream as prose right away (tools-enabled clients
    should not lose incremental streaming for plain-prose replies).

    Llama-3.1 JSON calls are whole-reply objects → a reply whose first
    non-space char is ``{`` buffers entirely. Hermes blocks start at
    ``<tool_call>`` → hold back from the first complete tag, or from a
    trailing partial prefix of it (the tag may still be arriving)."""
    if out.lstrip().startswith("{"):
        return 0
    i = out.find("<tool_call>")
    if i != -1:
        return i
    tag = "<tool_call>"
    for k in range(min(len(tag) - 1, len(out)), 0, -1):
        if out.endswith(tag[:k]):
            return len(out) - k
    return len(out)


def _parse_tool_calls(text: str) -> tuple[Optional[str], Optional[list]]:
    """Recognize the two dominant open-model tool-call output formats →
    (remaining content or None, OpenAI ``tool_calls`` list or None).

    - Hermes/Qwen: one or more ``<tool_call>{...}</tool_call>`` blocks;
      surrounding prose survives as content (OpenAI returns both)
    - Llama-3.1 JSON: the whole reply is one object with ``name`` and
      ``arguments``/``parameters``

    Anything else (prose, partial JSON) stays ordinary content — the
    caller must not lose text by over-eager parsing.
    """
    t = text.strip()
    raw = []
    content = None
    if "<tool_call>" in t:
        for m in _TOOL_CALL_RE.findall(t):
            try:
                obj = json.loads(m)
            except json.JSONDecodeError:
                return text, None
            if not (isinstance(obj, dict) and "name" in obj):
                return text, None
            raw.append(obj)
        remainder = _TOOL_CALL_RE.sub("", t).strip()
        if not raw or "<tool_call>" in remainder:
            # no complete block, or a TRUNCATED trailing block (length
            # cut mid-call): keep everything as plain content so the
            # client sees the real finish_reason, not a partial call
            return text, None
        content = remainder or None
    else:
        try:
            obj = json.loads(t)
        except json.JSONDecodeError:
            return text, None
        if not (
            isinstance(obj, dict) and "name" in obj
            and ("arguments" in obj or "parameters" in obj)
        ):
            return text, None
        raw.append(obj)
    calls = []
    for obj in raw:
        args = obj.get("arguments", obj.get("parameters", {}))
        calls.append({
            "id": f"call_{uuid.uuid4().hex[:24]}",
            "type": "function",
            "function": {
                "name": str(obj["name"]),
                "arguments": args if isinstance(args, str) else json.dumps(args),
            },
        })
    return content, calls


def build_app(
    engine: InferenceEngine,
    tokenizer: Tokenizer,
    model_name: str,
    chat_template: Optional[str] = None,
    qos_policy: Optional[qos.QoSPolicy] = None,
    watchdog_seconds: Optional[float] = None,
    deadline_default: Optional[float] = None,
    boot=_BOOT_FROM_ENV,
) -> web.Application:
    if qos_policy is None:
        qos_policy = qos.QoSPolicy.from_env()
    if watchdog_seconds is None:
        watchdog_seconds = _env_float("DTPU_ENGINE_WATCHDOG_SECONDS", 0.0)
    if deadline_default is None:
        deadline_default = _env_float("DTPU_REQUEST_DEADLINE_DEFAULT", 0.0)
    # boot recorder (obs/boot.py): the default is the process-global
    # one installed at import (DTPU_BOOT=0 leaves it None → every boot
    # touchpoint below is skipped). Multi-replica harnesses pass their
    # own — or an explicit None to opt a replica out, since one
    # process-wide recorder cannot describe several replicas' boots.
    if boot is _BOOT_FROM_ENV:
        boot = obs_boot.get_recorder()
    app = web.Application()
    app["boot"] = boot
    # where THIS replica runs (one of a host's chips, or the tp mesh),
    # not everything jax can see
    device_block = device_info(engine.devices)
    sched = Scheduler(
        engine, tokenizer, tenant_inflight=qos_policy.tenant_inflight,
        watchdog_seconds=watchdog_seconds, boot=boot,
    )
    app["scheduler"] = sched
    # live SLO windows over THIS replica's own registries (obs/slo.py;
    # no-op None under DTPU_SLO=0): /health embeds the rolling
    # TTFT/queue-wait/TPOT window summaries as `slo_windows`, which the
    # router's probe loop relays to the control plane's process_slo —
    # the probe is the transport, no new scrape protocol. Per-app (not
    # module-global) because test harnesses run several replicas in
    # one process.
    replica_slo_state = obs_slo.replica_slo(
        lambda: obs_slo.serve_signals(engine.metrics, get_qos_registry())
    )
    app["replica_slo"] = replica_slo_state

    def _is_resume(request) -> bool:
        """Router-asserted mid-stream-failover continuation. The header
        is trustworthy for the same reason X-DTPU-Tenant is: the
        proxy/gateway strip client-supplied values and the forwarder
        injects it only on a resume re-dispatch."""
        return request.headers.get(qos.RESUME_HEADER) == "1"

    def _request_deadline(request) -> Optional[Deadline]:
        """Arm the per-request wall-clock budget: the edge header wins,
        DTPU_REQUEST_DEADLINE_DEFAULT covers headerless requests, and
        no deadline is armed otherwise. Malformed values are ignored —
        a bad header must not 400 the data path."""
        raw = request.headers.get(qos.DEADLINE_HEADER)
        seconds = None
        if raw:
            try:
                seconds = max(0.0, float(raw))
            except (TypeError, ValueError):
                seconds = None
        if seconds is None and deadline_default > 0:
            seconds = deadline_default
        return None if seconds is None else Deadline(seconds)
    buckets = (
        qos.TenantBuckets(
            qos_policy.rps,
            qos_policy.effective_burst(),
            max_tenants=qos_policy.max_tenants,
        )
        if qos_policy.enabled
        else None
    )

    def _admit(request, span=tracing.NOOP_SPAN) -> Optional[web.Response]:
        """Tenant-bucket admission for one request → a 429 response
        with a monotone ``Retry-After``, or None when admitted. Runs
        before any tokenization/prefill so an over-budget tenant costs
        nothing but this check. The decision lands on ``span`` as an
        ``edge_admit`` event."""
        if _is_resume(request):
            # a resumed continuation was admitted — and charged — on
            # its original leg; charging again would double-count
            # dtpu_qos_admitted, and shedding it would kill a stream
            # the service already committed to
            return None
        # trust_header: the tenant header reaching this process is
        # proxy-asserted (the proxy/gateway strip client-supplied
        # values and inject the authenticated identity)
        tenant = qos.tenant_from_headers(request.headers, trust_header=True)
        hint = qos.edge_admit(
            qos_policy, buckets, tenant,
            run_name=model_name, fault_point="serve.admit", span=span,
        )
        if hint is None:
            return None
        return web.json_response(
            {"detail": "tenant request budget exhausted; retry later"},
            status=429,
            headers={"Retry-After": str(hint)},
        )

    def _admit_extra(request, extra: int) -> Optional[web.Response]:
        """The fan-out charge: ``n`` choices are n engine generations,
        but the pre-parse _admit spent one token. Charge the other n-1
        (weighted try_acquire) once ``n`` is known, so ``n=8`` cannot
        buy 8× a compliant tenant's decode budget for one token.

        A shed REFUNDS the pre-parse token — sheds must stay free of
        charge, or a compliant client retrying on the hint drains its
        own budget and watches hints grow instead of shrink. With the
        refund, the returned hint (deficit for ``extra`` pre-refund ==
        deficit for the full ``n`` post-refund) is the full-cost wait,
        so obeying it lands on n tokens — unless n can NEVER fit the
        burst, which is a 400 (a 429's Retry-After would be a promise
        no wait can keep), also refunded. ``serve.admit`` fires only
        in _admit — one deterministic fire per HTTP request."""
        if extra <= 0 or buckets is None or not qos_policy.enabled:
            return None
        tenant = qos.tenant_from_headers(request.headers, trust_header=True)
        burst = qos_policy.effective_burst()
        if 1 + extra > burst:
            buckets.bucket(tenant).refund(1.0)
            return web.json_response(
                {"detail": f"'n' exceeds this service's request budget "
                           f"(n tokens needed, burst is {int(burst)})"},
                status=400,
            )
        hint = qos.edge_admit(
            qos_policy, buckets, tenant, run_name=model_name,
            fault_point=None, cost=float(extra),
        )
        if hint is None:
            return None
        buckets.bucket(tenant).refund(1.0)
        return web.json_response(
            {"detail": "tenant request budget exhausted for n choices; "
                       "retry later"},
            status=429,
            headers={"Retry-After": str(hint)},
        )

    async def on_startup(_):
        sched.start()
        if boot is not None:
            # aiohttp fires on_startup once the site is about to accept
            # — the closest in-process anchor for "listener up"
            boot.mark("listener_up")

    async def on_cleanup(_):
        await sched.stop()

    app.on_startup.append(on_startup)
    app.on_cleanup.append(on_cleanup)

    async def health(request):
        """Liveness plus live load: queue depth, inflight count, and KV
        utilization from the engine's obs gauges — what the routing
        layer's probe loop reads to drive READY/DEGRADED transitions
        and least-loaded picks (dstack_tpu.routing.pool)."""
        e = sched.engine
        e.update_state_gauges()
        m = e.metrics
        body = {
            "status": "ok",
            "model": model_name,
            "device": device_block,
            "queue_depth": sched.pending.qsize(),
            "inflight": len(sched.by_slot) + len(sched.by_prefill),
            "active_slots": int(m.family("dtpu_serve_active_slots").value()),
            "max_slots": int(m.family("dtpu_serve_max_slots").value()),
            "kv_utilization": m.family(
                "dtpu_serve_kv_cache_utilization_ratio"
            ).value(),
            # prefix-cache occupancy: what the routing layer's probe
            # loop folds into its replica load snapshot so the
            # affinity score can tell a warm registry from a cold one
            # (routing/pool.py, serving.md §10)
            **e.prefix_stats(),
            # a replica wedged inside a profiler capture (multi-GB
            # trace writes stall the event loop) or a compile storm
            # must be VISIBLE to probes: is_tracing plus THIS ENGINE's
            # compile/recompile/post-mortem counts — read from the
            # engine's own registry, not the process-global recorder,
            # so multi-replica-in-one-process harnesses attribute a
            # storm to the replica actually having it
            "profiler_tracing": obs_profiling.is_tracing(),
            "flight": {
                "enabled": flight.enabled(),
                "warm": e.flight_warm,
                "compiles": int(
                    m.family("dtpu_serve_compiles_total").total()
                ),
                "recompiles": int(
                    m.family("dtpu_serve_recompiles_total").total()
                ),
                "postmortems": int(
                    m.family("dtpu_serve_postmortems_total").value()
                ),
            },
        }
        if replica_slo_state is not None:
            # rolling per-window TTFT/queue-wait/TPOT bucket deltas +
            # request/error/shed counts: the probe loop relays these to
            # process_slo for fleet burn-rate evaluation (server.md
            # "SLO & alerting")
            body["slo_windows"] = replica_slo_state.health_windows()
        if boot is not None:
            # the first /health this process answers IS its readiness
            # probe (probes are the only callers): mark time-to-ready
            # once, then embed the TTFST decomposition + boot_id. The
            # probe loop ingests the block fleet-side and invalidates
            # affinity on a boot_id change (the authoritative restart
            # signal — a restarted, re-warmed replica never shows
            # prefix_slots=0).
            boot.mark(obs_boot.READY_MARK)
            body["boot"] = boot.health_block(warm=e.flight_warm)
        return web.json_response(body)

    async def models(request):
        return web.json_response(
            {
                "object": "list",
                "data": [{"id": model_name, "object": "model", "owned_by": "dstack-tpu"}],
            }
        )

    async def metrics(request):
        """Prometheus text from the engine's obs registry (TTFT/TPOT/
        throughput histograms, queue/batch/KV gauges): the shim's
        metrics relay scrapes this like any service and the server's
        prometheus plane re-exports it."""
        e = sched.engine
        e.update_state_gauges()
        e.metrics.family("dtpu_serve_queue_depth").set(sched.pending.qsize())
        # one page: engine families + this process's dtpu_qos_* edge
        # counters (shed/admitted per tenant digest, queue wait by
        # priority class) + tracing bookkeeping — the shim relay
        # scrapes them together
        if replica_slo_state is not None:
            # keep the local burn gauges fresh even when nothing probes
            # /health (ad-hoc replicas scraped directly)
            replica_slo_state.maybe_tick()
        return web.Response(
            text=e.metrics.render() + get_qos_registry().render()
            + get_trace_registry().render()
            + obs_slo.get_slo_registry().render()
            + flight.get_flight_registry().render()
            + obs_boot.get_boot_registry().render(),
            content_type="text/plain",
        )

    async def debug_traces(request):
        """Completed traces from this replica's in-process ring: the
        serve-side half of a stitched request trace (``?id=`` /
        ``?slowest=N`` — same contract as the server's and gateway's
        endpoints, docs/reference/server.md "Tracing")."""
        return web.json_response(tracing.debug_payload(request.query))

    async def debug_flight(request):
        """The engine flight recorder: per-step timeline ring, compile
        accounting, device-memory watermarks, and post-mortem
        snapshots (``?limit=`` / ``?postmortems=`` — same exposure
        gate as ``/debug/traces``; docs/reference/server.md "Flight
        recorder")."""
        return web.json_response(flight.debug_payload(request.query))

    async def debug_boot(request):
        """The boot recorder: boot_id, the full stage timeline
        (``?limit=``), the /health-shaped summary, and this engine's
        boot-compile manifest with its warmup-coverage verdict
        (docs/reference/server.md "Boot & cold start")."""
        # an app built with boot=None OPTED OUT (multi-replica
        # harnesses): report disabled rather than falling back to the
        # process-global recorder, which describes a different replica
        if boot is None:
            return web.json_response({"enabled": False, "timeline": []})
        payload = obs_boot.debug_payload(request.query, recorder=boot)
        if payload.get("enabled"):
            manifest = sorted(sched.engine.compile_manifest())
            payload["compile_manifest"] = {
                "warm": sched.engine.flight_warm,
                "variants": manifest,
                "gap_compiles": int(
                    sched.engine.metrics.family(
                        "dtpu_serve_warmup_gap_compiles_total"
                    ).total()
                ),
            }
        return web.json_response(payload)

    import dataclasses as _dc

    async def _run(
        prompt: str, payload: dict, request, resume_text=None,
        span=tracing.NOOP_SPAN,
    ):
        gen = _gen_params(payload, tokenizer)
        if span.recording:
            # engine-side exemplar plumbing: the TTFT/TPOT histograms
            # attach this trace id to the bucket the request lands in
            gen.trace_id = span.trace_id
        prompt_ids = tokenizer.encode(prompt)
        resumed_ids: list = []
        if resume_text:
            # mid-stream failover continuation: a partially-generated
            # sequence is just a longer prompt — append the delivered
            # text (the prefix cache turns the re-prefill into a packed
            # resume), shrink the generation budget by what already
            # shipped, and replay a seeded stream's PRNG advance so the
            # continuation samples the ORIGINAL stream's tokens.
            # n_resumed is derived by RE-tokenizing the splice: exact
            # whenever the delivered text re-encodes to the tokens the
            # original stream drew (byte tokenizer on ASCII; canonical
            # BPE output) — a boundary merge shifts both the context
            # and the skip count together and the stream may diverge
            # from the unbroken run (serving.md §9's stated limit)
            full_ids = tokenizer.encode(prompt + resume_text)
            n_resumed = max(0, len(full_ids) - len(prompt_ids))
            resumed_ids = full_ids[len(full_ids) - n_resumed:]
            gen.max_new_tokens = max(1, gen.max_new_tokens - n_resumed)
            if gen.seed is not None:
                gen.seed_skip = n_resumed
            prompt_ids = full_ids
            engine.metrics.family("dtpu_serve_resumed_requests_total").inc(1)
        tenant = qos.tenant_from_headers(request.headers, trust_header=True)
        req = _Request(
            prompt_ids,
            gen,
            tenant=tenant,
            priority=qos.parse_priority_class(
                request.headers.get(qos.PRIORITY_HEADER)
                or payload.get("priority")
            ),
        )
        # stop-string continuity across the resume splice: the
        # delivered tail participates in the bounded match window
        req.gen_ids = list(resumed_ids)
        req.span = span
        if resume_text:
            span.set(resumed=True, resumed_tokens=len(resumed_ids))
        if buckets is not None and qos_policy.enabled and not _is_resume(request):
            # remember the charged bucket so a pre-first-token abort
            # (disconnect/deadline/watchdog) can refund it; resumed
            # continuations were never charged here
            req.bucket = buckets.bucket(tenant)
        req.deadline = _request_deadline(request)
        await sched.submit(req)
        return req

    def _n_choices(payload: dict):
        """Validated OpenAI ``n`` (choices per request) → int or an
        error response. Explicit null means default, like every other
        optional param."""
        n = payload.get("n")
        if n is None:
            n = 1
        if not isinstance(n, int) or isinstance(n, bool) or not 1 <= n <= 8:
            return web.json_response(
                {"detail": "'n' must be an integer in [1, 8]"}, status=400
            )
        if n > 1 and payload.get("stream"):
            return web.json_response(
                {"detail": "streaming with n > 1 is not supported"}, status=400
            )
        return n

    async def _collect(req) -> list:
        ids = []
        try:
            while True:
                tok = await req.queue.get()
                if tok is None:
                    break
                ids.append(tok)
        finally:
            sched.cancel(req)
        return ids

    async def _fan_out(first_req, n: int):
        """Submit the remaining n-1 choices (prompt tokenized once, gen
        params copied with a per-choice seed offset), collect all →
        (reqs, id_lists, total_completion_tokens) or an error response."""
        reqs = [first_req]
        for i in range(1, n):
            gen = _dc.replace(first_req.gen)
            if gen.seed is not None:
                gen.seed += i  # distinct deterministic stream per choice
            req = _Request(
                list(first_req.prompt_ids), gen,
                tenant=first_req.tenant, priority=first_req.priority,
            )
            # each choice charged one bucket token at admission — each
            # refunds its own on a pre-first-token abort
            req.bucket = first_req.bucket
            req.deadline = first_req.deadline
            # fan-out choices share the request's root trace: their
            # queue/prefill/decode phases land as siblings under it
            req.span = first_req.span
            await sched.submit(req)
            reqs.append(req)
        id_lists = await asyncio.gather(*(_collect(r) for r in reqs))
        failed = next((r for r in reqs if r.error), None)
        if failed is not None:
            headers = {}
            if failed.retry_after is not None and failed.error_status in (
                429, 503,
            ):
                headers["Retry-After"] = str(failed.retry_after)
            return web.json_response(
                {"detail": failed.error},
                status=failed.error_status,
                headers=headers,
            )
        total = sum(len(ids) for ids in id_lists)
        return reqs, id_lists, total

    def _start_trace(request, endpoint: str):
        """The serve-side root span: parented to the router's dispatch
        leg via the proxy-asserted ``X-DTPU-Trace`` header (stripped
        from client requests by the forwarder and blanked by nginx —
        the same trust chain as ``X-DTPU-Tenant``); a headerless
        direct hit starts a fresh trace. Span attrs carry identifiers
        and counts only, never prompt or completion text."""
        return tracing.span(
            "serve.request",
            trace=request.headers.get(tracing.TRACE_HEADER),
            endpoint=endpoint,
        )

    async def chat_completions(request):
        root = _start_trace(request, "chat")
        try:
            resp = await _chat_completions(request, root)
            if root.recording and not resp.prepared:
                resp.headers[tracing.TRACE_HEADER] = root.trace_id
            return resp
        finally:
            root.end()

    async def _chat_completions(request, root):
        from dstack_tpu.proxy.model_tgi import TGIAdapterError

        shed = _admit(request, span=root)
        if shed is not None:
            return shed
        try:
            payload = await request.json()
        except Exception:
            return web.json_response({"detail": "invalid JSON body"}, status=400)
        bad = _bad_sampling_params(payload)
        if bad:
            return web.json_response({"detail": bad}, status=400)
        resume_text = None
        if _is_resume(request):
            r = payload.get("dtpu_resume")
            if isinstance(r, dict) and isinstance(r.get("text"), str) and r["text"]:
                if _logprobs_requested(payload) is not None:
                    # logprob entries cannot align across the splice —
                    # the router never resumes logprob streams; refuse
                    # loudly rather than return misaligned arrays
                    return web.json_response(
                        {"detail": "a resumed continuation cannot carry "
                                   "logprobs"},
                        status=400,
                    )
                resume_text = r["text"]
        messages = payload.get("messages")
        if not isinstance(messages, list) or not messages or not all(
            _valid_chat_message(m) for m in messages
        ):
            return web.json_response(
                {"detail": "'messages' must be [{role, content}, ...] "
                           "(assistant tool_calls / role=tool allowed)"},
                status=400,
            )
        tools = payload.get("tools")
        if tools is not None and not (
            isinstance(tools, list)
            and all(isinstance(t, dict) for t in tools)
        ):
            return web.json_response(
                {"detail": "'tools' must be a list of objects"}, status=400
            )
        tool_choice = payload.get("tool_choice")
        if tool_choice == "none":
            tools = None  # opt-out: render no tools, parse nothing
        elif tool_choice not in (None, "auto"):
            # 'required' / named-function forcing needs constrained
            # decoding — refuse loudly rather than silently not forcing
            return web.json_response(
                {"detail": "tool_choice supports 'auto' and 'none' only"},
                status=400,
            )
        rf = payload.get("response_format")
        if rf is not None:
            kind = rf.get("type") if isinstance(rf, dict) else None
            if kind == "json_schema":
                # schema enforcement needs grammar-constrained decoding
                # — refuse loudly rather than return unconstrained text
                return web.json_response(
                    {"detail": "response_format 'json_schema' is not "
                               "supported (no constrained decoding); "
                               "'json_object' and 'text' are"},
                    status=400,
                )
            if kind not in (None, "text", "json_object"):
                return web.json_response(
                    {"detail": "response_format.type must be 'text' or "
                               "'json_object'"},
                    status=400,
                )
            if kind == "json_object":
                # best-effort JSON mode: steer via an instruction the
                # template renders as the LAST system turn (the same
                # mechanism TGI/older vLLM used pre-grammar); output is
                # NOT validated — documented in docs/guides/serving.md
                messages = list(messages) + [{
                    "role": "system",
                    "content": "Respond ONLY with a valid JSON object. "
                               "No prose, no markdown fences.",
                }]
        try:
            prompt = render_chat(
                messages, chat_template or DEFAULT_CHAT_TEMPLATE, tools=tools
            )
        except TGIAdapterError as e:
            return web.json_response({"detail": str(e)}, status=e.status)
        n = _n_choices(payload)
        if not isinstance(n, int):
            return n
        shed = _admit_extra(request, n - 1)
        if shed is not None:
            return shed
        req = await _run(
            prompt, payload, request, resume_text=resume_text, span=root
        )
        completion_id = f"chatcmpl-{uuid.uuid4().hex}"
        created = int(time.time())
        if payload.get("stream"):
            stream_headers = {
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
            }
            if root.recording:
                # headers commit at prepare(): echo the trace id now
                stream_headers[tracing.TRACE_HEADER] = root.trace_id
            resp = web.StreamResponse(headers=stream_headers)
            await resp.prepare(request)
            # deltas come from re-decoding the accumulated ids: per-token
            # decode would corrupt multi-byte UTF-8 and BPE boundaries.
            # Trailing replacement chars (split multi-byte sequences) are
            # held back until the next token completes them; so is any
            # trailing prefix of a stop string (OpenAI semantics: no
            # part of a stop sequence is ever delivered).
            ids: list[int] = []
            sent = ""
            lp_top = req.gen.logprobs or 0
            lp_emitted = 0

            m_detok = engine.metrics.family("dtpu_serve_detokenize_seconds")
            m_write = engine.metrics.family("dtpu_serve_stream_write_seconds")
            m_lag = engine.metrics.family("dtpu_serve_first_delta_lag_seconds")
            m_tokens = engine.metrics.family("dtpu_serve_stream_tokens_total")
            m_overlapped = engine.metrics.family(
                "dtpu_serve_stream_tokens_overlapped_total"
            )
            # the per-token timings are only noted on the token path:
            # the handlers share the GIL with the worker thread that
            # enqueues the next engine call. The histograms take them
            # when the handler parks for its next token, a loop
            # iteration later.
            detok_s: list[float] = []
            write_s: list[float] = []
            # hand-over → the end of the delta that answers it, once a
            # request a hand-over (the scheduler's stamp, cleared here)
            lag_s: list[float] = []
            # tokens taken off the queue, and those of them taken while
            # an engine call was in flight (the turn the scheduler
            # leaves the handlers: inside the next call's await)
            taken = [0, 0]
            loop = asyncio.get_running_loop()

            def observe_noted() -> None:
                for hist, noted in (
                    (m_detok, detok_s), (m_write, write_s), (m_lag, lag_s)
                ):
                    for v in noted:
                        hist.observe(v)
                    noted.clear()
                if taken[0]:
                    m_tokens.inc(taken[0])
                    m_overlapped.inc(taken[1])
                    taken[:] = 0, 0

            def detokenize(hold: bool) -> str:
                """All ids so far → deliverable text (``hold``: minus a
                tail that may still grow into a stop string)."""
                t0 = time.perf_counter()
                with obs_profiling.span("dtpu.stream.detokenize"):
                    full = tokenizer.decode(ids)
                    while full.endswith("�"):
                        full = full[:-1]
                    full = _truncate_stop(full, req.gen.stop)
                    if hold:
                        full = full[
                            : len(full) - _stop_holdback(full, req.gen.stop)
                        ]
                detok_s.append(time.perf_counter() - t0)
                return full

            async def emit(delta: str, tool_calls=None) -> None:
                nonlocal lp_emitted
                t0 = time.perf_counter()
                with obs_profiling.span("dtpu.stream.write"):
                    d = {"role": "assistant", "content": delta}
                    if tool_calls is not None:
                        d["tool_calls"] = tool_calls
                    choice = {
                        "index": 0,
                        "delta": d,
                        "finish_reason": None,
                    }
                    if req.gen.logprobs is not None:
                        # entries for the tokens consumed since the last
                        # chunk (delta boundaries are char-diffs, so the
                        # token alignment is approximate at holdback edges)
                        hi = len(req.logprob_entries)
                        choice["logprobs"] = {
                            "content": _chat_logprob_entries(
                                req, tokenizer, lp_top, lp_emitted, hi
                            )
                        }
                        lp_emitted = hi
                    chunk = {
                        "id": completion_id,
                        "object": "chat.completion.chunk",
                        "created": created,
                        "model": model_name,
                        "choices": [choice],
                    }
                    await resp.write(b"data: " + json.dumps(chunk).encode() + b"\n\n")
                write_s.append(time.perf_counter() - t0)

            stream_finish = None
            try:
                while True:
                    if detok_s and req.queue.empty():
                        loop.call_soon(observe_noted)
                    tok = await req.queue.get()
                    if tok is None:
                        break
                    ids.append(tok)
                    taken[0] += 1
                    taken[1] += sched.calls_in_flight > 0
                    out = detokenize(hold=True)
                    if tools:
                        # stream prose up to the first point that could
                        # still become a tool call; only the candidate
                        # region buffers for end-of-stream parsing
                        out = out[:_tool_stream_safe_len(out)]
                    delta = out[len(sent):]
                    if not delta:
                        continue
                    sent = out
                    await emit(delta)
                    if req.handed_at is not None:
                        lag_s.append(time.perf_counter() - req.handed_at)
                        req.handed_at = None
                # generation over: flush held-back text that never
                # completed into a stop string (minus any true stop cut)
                if ids and not tools:
                    tail = detokenize(hold=False)[len(sent):]
                    if tail:
                        await emit(tail)
                elif ids and tools:
                    # parse only the HELD-BACK tail: any prose before it
                    # already streamed incrementally
                    rest = detokenize(hold=False)[len(sent):]
                    content, tool_calls = (
                        _parse_tool_calls(rest) if rest else (None, None)
                    )
                    if tool_calls:
                        await emit(content, tool_calls=[
                            {**c, "index": ci}
                            for ci, c in enumerate(tool_calls)
                        ])
                        stream_finish = "tool_calls"
                    elif rest:
                        await emit(rest)
            finally:
                sched.cancel(req)  # no-op when finished; frees the slot on disconnect
                observe_noted()
            if req.error:
                await resp.write(
                    b"data: " + json.dumps({"error": req.error}).encode() + b"\n\n"
                )
                await resp.write(b"data: [DONE]\n\n")
                return resp
            final = {
                "id": completion_id,
                "object": "chat.completion.chunk",
                "created": created,
                "model": model_name,
                "choices": [
                    {
                        "index": 0,
                        "delta": {},
                        "finish_reason": (
                            stream_finish or req.finish_reason or "stop"
                        ),
                    }
                ],
            }
            await resp.write(b"data: " + json.dumps(final).encode() + b"\n\n")
            await resp.write(b"data: [DONE]\n\n")
            return resp
        fanned = await _fan_out(req, n)
        if not isinstance(fanned, tuple):
            return fanned
        reqs, id_lists, total_completion = fanned
        choices = []
        for i, (r, ids) in enumerate(zip(reqs, id_lists)):
            text = _truncate_stop(tokenizer.decode(ids), r.gen.stop)
            content, tool_calls = (
                _parse_tool_calls(text) if tools else (text, None)
            )
            if tool_calls:
                message = {
                    "role": "assistant", "content": content,
                    "tool_calls": tool_calls,
                }
                finish = "tool_calls"
            else:
                message = {"role": "assistant", "content": text}
                finish = r.finish_reason or "stop"
            choice = {
                "index": i,
                "message": message,
                "finish_reason": finish,
            }
            if r.gen.logprobs is not None:
                choice["logprobs"] = _format_chat_logprobs(
                    r, tokenizer, r.gen.logprobs, text
                )
            choices.append(choice)
        return web.json_response(
            {
                "id": completion_id,
                "object": "chat.completion",
                "created": created,
                "model": model_name,
                "choices": choices,
                "usage": {
                    "prompt_tokens": len(req.prompt_ids),
                    "completion_tokens": total_completion,
                    "total_tokens": len(req.prompt_ids) + total_completion,
                },
            }
        )

    async def completions(request):
        root = _start_trace(request, "completions")
        try:
            resp = await _completions(request, root)
            if root.recording and not resp.prepared:
                resp.headers[tracing.TRACE_HEADER] = root.trace_id
            return resp
        finally:
            root.end()

    async def _completions(request, root):
        shed = _admit(request, span=root)
        if shed is not None:
            return shed
        try:
            payload = await request.json()
        except Exception:
            return web.json_response({"detail": "invalid JSON body"}, status=400)
        prompt = payload.get("prompt")
        if not isinstance(prompt, str):
            return web.json_response({"detail": "'prompt' required"}, status=400)
        bad = _bad_sampling_params(payload)
        if bad:
            return web.json_response({"detail": bad}, status=400)
        n = _n_choices(payload)
        if not isinstance(n, int):
            return n
        shed = _admit_extra(request, n - 1)
        if shed is not None:
            return shed
        first = await _run(prompt, payload, request, span=root)
        fanned = await _fan_out(first, n)
        if not isinstance(fanned, tuple):
            return fanned
        reqs, id_lists, total_completion = fanned
        choices = []
        for i, (r, ids) in enumerate(zip(reqs, id_lists)):
            choice = {
                "index": i,
                "text": _truncate_stop(tokenizer.decode(ids), r.gen.stop),
                "finish_reason": r.finish_reason or "stop",
            }
            if r.gen.logprobs is not None:
                choice["logprobs"] = _format_completions_logprobs(
                    r, tokenizer, r.gen.logprobs, choice["text"],
                )
            choices.append(choice)
        return web.json_response(
            {
                "id": f"cmpl-{uuid.uuid4().hex}",
                "object": "text_completion",
                "created": int(time.time()),
                "model": model_name,
                "choices": choices,
                "usage": {
                    "prompt_tokens": len(reqs[0].prompt_ids),
                    "completion_tokens": total_completion,
                    "total_tokens": len(reqs[0].prompt_ids) + total_completion,
                },
            }
        )

    # /v1/embeddings: mean-pooled, L2-normalized final hidden states —
    # decoder-only-LLM-as-embedder convention (e5-mistral-style pooling
    # without the instruction prefix). One jitted fn per power-of-2
    # length bucket; compiled lazily, reused across requests.
    import functools as _ft

    import jax as _jax
    import jax.numpy as _jnp

    from dstack_tpu.models import llama as _llama

    _embed_cfg = _llama.dataclasses.replace(engine.config, remat=False)

    @_ft.lru_cache(maxsize=16)
    def _embed_fn(padded: int):
        def fn(params, tokens, n):  # tokens [1, padded], n [] int32
            h = _llama.forward(
                params, tokens, _embed_cfg, return_hidden=True
            ).astype(_jnp.float32)  # [1, P, H]
            m = (_jnp.arange(tokens.shape[1]) < n)[None, :, None]
            pooled = _jnp.sum(h * m, axis=1)[0] / _jnp.maximum(n, 1)
            return pooled / _jnp.maximum(
                _jnp.linalg.norm(pooled), 1e-9
            )

        return _jax.jit(fn)

    async def embeddings(request):
        shed = _admit(request)
        if shed is not None:
            return shed
        try:
            payload = await request.json()
        except Exception:
            return web.json_response({"detail": "invalid JSON body"}, status=400)
        inputs = payload.get("input")
        if isinstance(inputs, str):
            inputs = [inputs]
        if not isinstance(inputs, list) or not all(
            isinstance(s, str) for s in inputs
        ) or not inputs:
            return web.json_response(
                {"detail": "'input' must be a string or list of strings"},
                status=400,
            )
        id_lists = [tokenizer.encode(text) or [0] for text in inputs]
        for i, ids in enumerate(id_lists):
            if len(ids) > engine.max_seq:
                # OpenAI returns a context-length error rather than
                # silently embedding a truncated tail
                return web.json_response(
                    {"detail": f"input {i} has {len(ids)} tokens, over "
                               f"the model's {engine.max_seq} maximum"},
                    status=400,
                )
        total_tokens = sum(len(ids) for ids in id_lists)

        def _compute():
            # dispatch EVERY forward before the first device_get: JAX's
            # async dispatch then pipelines the batch instead of paying
            # a host-device sync per item
            vecs = []
            for ids in id_lists:
                padded = 16
                while padded < len(ids):
                    padded *= 2
                toks = _jnp.asarray(
                    [ids + [0] * (padded - len(ids))], _jnp.int32
                )
                vecs.append(_embed_fn(padded)(
                    engine.params, toks, _jnp.asarray(len(ids), _jnp.int32)
                ))
            # dtpu: noqa[DTPU002] ONE batched pull after every forward dispatched — the pipelined design this comment block describes
            return _jax.device_get(vecs)

        # off the event loop: a new length bucket compiles for seconds,
        # which must not stall other connections' streams
        host_vecs = await asyncio.to_thread(_compute)
        data = [
            {
                "object": "embedding",
                "index": i,
                "embedding": [float(v) for v in vec],
            }
            for i, vec in enumerate(host_vecs)
        ]
        return web.json_response({
            "object": "list",
            "data": data,
            "model": model_name,
            "usage": {
                "prompt_tokens": total_tokens,
                "total_tokens": total_tokens,
            },
        })

    app.router.add_get("/health", health)
    app.router.add_get("/metrics", metrics)
    app.router.add_get("/debug/traces", debug_traces)
    app.router.add_get("/debug/flight", debug_flight)
    app.router.add_get("/debug/boot", debug_boot)
    app.router.add_get("/v1/models", models)
    app.router.add_post("/v1/chat/completions", chat_completions)
    app.router.add_post("/v1/completions", completions)
    app.router.add_post("/v1/embeddings", embeddings)

    if obs_profiling.profiler_dir():
        # on-demand JAX profiler capture, registered ONLY when
        # DTPU_PROFILER_DIR is set (an always-on unauthenticated knob
        # that writes multi-GB traces would be a production footgun)
        async def profiler_start(request):
            try:
                return web.json_response(obs_profiling.start_trace())
            except RuntimeError as e:
                return web.json_response({"detail": str(e)}, status=409)

        async def profiler_stop(request):
            try:
                return web.json_response(obs_profiling.stop_trace())
            except RuntimeError as e:
                return web.json_response({"detail": str(e)}, status=409)

        app.router.add_post("/debug/profiler/start", profiler_start)
        app.router.add_post("/debug/profiler/stop", profiler_stop)
    return app


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="llama-3.2-1b", help="config name (models/llama.py CONFIGS)")
    p.add_argument("--weights", default=None, help=".npz from finetune (random init when omitted)")
    p.add_argument(
        "--hf-model", default=None,
        help="HF save_pretrained dir (llama/qwen2/mistral/gemma/gemma2/"
             "mixtral): loads config+weights+tokenizer, overrides --model",
    )
    p.add_argument(
        "--tokenizer", default=None,
        help="'byte' or a HF tokenizer path (default: the --hf-model "
             "dir when it ships a tokenizer, else byte)",
    )
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--max-seq", type=int, default=2048)
    p.add_argument("--chat-template", default=None, help="jinja chat template override")
    p.add_argument(
        "--platform", default=None,
        help="run on this jax platform (cpu for tests and rehearsals); "
             "without it the server needs an accelerator and exits "
             "non-zero when there is none",
    )
    p.add_argument(
        "--tp", type=int, default=0,
        help="tensor-parallel ways (default: all local devices)",
    )
    p.add_argument(
        "--quantize", default=None, choices=["int8"],
        help="weight-only quantization: halves HBM per weight read "
             "(decode is bandwidth-bound)",
    )
    p.add_argument(
        "--compile-cache", default=None,
        help="persistent XLA compile-cache dir (volume-mounted: restarts "
             "skip prefill/decode compiles, cutting time-to-first-token); "
             "default: JAX_COMPILATION_CACHE_DIR, else one fixed path in "
             "the checkout",
    )
    p.add_argument(
        "--prefill-pack", type=int, default=4,
        help="max concurrent prompt chunks packed into one prefill "
             "dispatch (a burst of N arrivals costs ceil(N/pack) "
             "dispatches per chunk wave instead of N; 0/1 = serial "
             "per-prompt prefill)",
    )
    p.add_argument(
        "--spec-draft", type=int, default=4,
        help="prompt-lookup speculative decoding draft length for greedy "
             "requests (0 disables)",
    )
    p.add_argument(
        "--turbo-steps", type=int, default=8,
        help="device-side decode steps per dispatch for all-greedy "
             "batches (amortizes the host round trip; 0/1 disables — "
             "streaming then delivers token-by-token)",
    )
    p.add_argument(
        "--turbo-depth", type=int, default=1,
        help="macro-steps kept in flight per host round trip once the "
             "adaptive turbo cap is fully open (pipelined turbo: >1 "
             "amortizes the host↔device round trip; costs up to "
             "depth×turbo-steps extra masked steps when every slot "
             "finishes early)",
    )
    p.add_argument(
        "--decode-kernel", default=None, choices=["einsum", "flash"],
        help="ask for one decode attention form: the masked einsum over "
             "the full cache row, or the ragged pallas kernel "
             "(ops/flash_decode — each live slot reads only the key "
             "blocks it holds; non-MLA models; runs per-shard under "
             "tensor parallelism). Default: the server chooses by the "
             "model's shape (ops/flash_decode.reads_live_keys: on the "
             "TPU the kernel for grouped-query bf16 layers over a plain "
             "row buffer, else the einsum)",
    )
    p.add_argument(
        "--no-warmup", action="store_true",
        help="skip the startup compile warmup (first request then pays "
             "the prefill/decode XLA compiles in its TTFT)",
    )
    p.add_argument(
        "--kv-quant", default=None, choices=["int8"],
        help="int8 KV cache with per-(token, head) scales: ~2x less "
             "decode HBM traffic and 2x the context per slot, at a "
             "small quantization accuracy cost (not for MLA models)",
    )
    p.add_argument(
        "--no-prefix-cache", action="store_true",
        help="disable automatic prefix caching (KV-row reuse across "
             "requests sharing a chunk-aligned prompt prefix)",
    )
    p.add_argument(
        "--qos-rps", type=float, default=None,
        help="per-tenant sustained requests/second; over-budget tenants "
             "get 429 + Retry-After (default: DTPU_QOS_RPS env, 0 = off)",
    )
    p.add_argument(
        "--qos-burst", type=float, default=None,
        help="per-tenant bucket capacity (default: DTPU_QOS_BURST env, "
             "0 = 2x rps)",
    )
    p.add_argument(
        "--qos-tenant-inflight", type=int, default=None,
        help="max engine slots one tenant may hold concurrently "
             "(default: DTPU_QOS_TENANT_INFLIGHT env, 0 = off)",
    )
    args = p.parse_args(argv)

    from dstack_tpu.utils.logging import configure_logging

    configure_logging()

    import jax

    device = select_platform(args.platform)
    logger.info(
        "device: %s, compile cache: %s",
        json.dumps(device), enable_compile_cache(args.compile_cache),
    )

    from dstack_tpu.models import llama

    hf_params = None
    if args.hf_model:
        from dstack_tpu.models.convert_hf import load_checkpoint

        # boot stage: the HF path reads config AND weights in one
        # pass, so the whole checkpoint read is the weights_load
        # stage (bytes → bytes/s is the number a streamed-weights
        # optimization would move)
        with obs_boot.stage("weights_load", source="hf") as _bs:
            config, hf_params = load_checkpoint(args.hf_model)
            _bs.set(bytes=sum(
                int(getattr(leaf, "nbytes", 0))
                for leaf in jax.tree_util.tree_leaves(hf_params)
            ))
        args.model = Path(args.hf_model).name
        if args.tokenizer is None and any(
            (Path(args.hf_model) / f).exists()
            for f in ("tokenizer.json", "tokenizer_config.json", "tokenizer.model")
        ):
            args.tokenizer = args.hf_model  # tokenizer ships alongside
        logger.info(
            "loaded HF checkpoint %s (%.2fB params)",
            args.hf_model, config.num_params() / 1e9,
        )
    else:
        with obs_boot.stage("config_load", model=args.model):
            config = llama.CONFIGS[args.model]
    tp = args.tp or len(jax.devices())
    mesh = None
    if tp > 1:
        from dstack_tpu.parallel.mesh import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(dp=1, fsdp=1, tp=tp))
        logger.info("tensor-parallel serving over %d devices", tp)
    # boot: device placement/init sums into the same weights_load
    # stage as the checkpoint read — together they are the total
    # weights cost of the boot
    with obs_boot.stage("weights_load", phase="device_put"):
        if hf_params is not None:
            # host (numpy) tree from convert_hf; with a mesh the engine
            # device_puts it straight into sharded buffers (never whole
            # on chip 0), without one a single put avoids per-call
            # transfers
            if mesh is not None and args.weights:
                # the --weights overlay below reads each leaf's
                # .sharding — shard the tree now (same shardings the
                # engine would use)
                from dstack_tpu.parallel.sharding import default_rules, tree_shardings

                params = jax.device_put(
                    hf_params,
                    tree_shardings(llama.param_specs(config), mesh, default_rules()),
                )
            else:
                params = hf_params if mesh is not None else jax.device_put(hf_params)
        elif mesh is not None:
            # init directly under the mesh shardings: a 70B never fits
            # chip 0
            from dstack_tpu.serve.engine import sharded_params

            params = sharded_params(config, mesh)
        else:
            params = llama.init_params(config, jax.random.key(0))
    if args.weights:
        import numpy as np

        with obs_boot.stage("weights_load", source="npz") as _bs:
            flat = dict(np.load(args.weights))
            _bs.set(bytes=sum(
                int(v.nbytes) for k, v in flat.items() if k != "step"
            ))
            import jax.numpy as jnp

            if any("/" not in k and "." in k for k in flat if k != "step"):
                raise SystemExit(
                    f"{args.weights} looks like a LoRA adapter file "
                    "(finetune without --full); the server loads full "
                    "checkpoints — re-run finetune with --full or merge "
                    "the adapters into the base weights first"
                )

            def set_path(tree, path, value):
                *parents, leaf = path
                for k in parents:
                    tree = tree[k]
                old = tree[leaf]
                tree[leaf] = jax.device_put(
                    jnp.asarray(value, old.dtype), old.sharding
                )

            for key, value in flat.items():
                if key == "step":
                    continue
                set_path(params, key.split("/"), value)
        logger.info("loaded %d weight arrays from %s", len(flat), args.weights)

    if args.quantize == "int8":
        from dstack_tpu.models.quant import quantize_tree

        params = quantize_tree(params, config)
        logger.info("weights quantized to int8 (per-output-channel scales)")
    with obs_boot.stage("engine_init"):
        engine = InferenceEngine(
            config, params, max_batch=args.max_batch, max_seq=args.max_seq,
            mesh=mesh, spec_draft=args.spec_draft,
            prefill_pack=args.prefill_pack,
            turbo_steps=args.turbo_steps,
            turbo_depth=args.turbo_depth,
            prefix_cache=not args.no_prefix_cache,
            kv_quant=args.kv_quant,
            decode_kernel=args.decode_kernel,
        )
    # tokenizer first: it's cheap and fail-fast — a typo'd path must
    # not cost a full compile warmup before erroring
    with obs_boot.stage("tokenizer_load"):
        tokenizer = load_tokenizer(args.tokenizer or "byte")
    if not args.no_warmup:
        _warmup_engine(engine)
    env_policy = qos.QoSPolicy.from_env()
    qos_policy = qos.QoSPolicy(
        rps=env_policy.rps if args.qos_rps is None else args.qos_rps,
        burst=env_policy.burst if args.qos_burst is None else args.qos_burst,
        tenant_inflight=(
            env_policy.tenant_inflight
            if args.qos_tenant_inflight is None
            else args.qos_tenant_inflight
        ),
        max_tenants=env_policy.max_tenants,
    )
    if qos_policy.enabled or qos_policy.tenant_inflight:
        logger.info(
            "qos: %.3g rps/tenant (burst %.3g), tenant inflight cap %d",
            qos_policy.rps, qos_policy.effective_burst(),
            qos_policy.tenant_inflight,
        )
    app = build_app(
        engine, tokenizer, args.model, args.chat_template,
        qos_policy=qos_policy,
    )
    logger.info("openai server: %s on :%d", args.model, args.port)
    web.run_app(app, host="0.0.0.0", port=args.port, print=None)
    return 0


def _warmup_engine(engine) -> None:
    """Compile the kernels real requests will hit, at STARTUP instead
    of inside first-request TTFT: the smallest and full prefill-chunk
    buckets, EVERY power-of-two turbo decode_loop variant (the
    macro-step is budget-capped, so short/tail generations pick smaller
    variants), the sampled-path decode + full-batch sampler, and — when
    speculation is on — the verify step. With --compile-cache mounted
    this run also populates the persistent cache, so restarts skip even
    the warmup cost."""
    t0 = time.time()
    spec = engine.spec_draft
    engine.spec_draft = 0
    full = [(i % 251) + 1 for i in range(engine.prefill_chunk)]
    runs = 0

    def run(prompt, gen):
        nonlocal runs
        runs += 1
        slot, _ = engine.add_request(prompt, gen)
        while engine.active[slot]:
            engine.step()
        engine.release(slot)

    # boot stage: the compile-grid warmup — every run() below inserts
    # its variants into the engine's boot-compile manifest via the
    # watch_jit on_compile hook, so the manifest IS the coverage
    # record of this stage
    with obs_boot.stage("warmup_compile") as _boot_stage:
        # full prefill chunk + the largest turbo variant (and steps=1
        # tail)
        run(full, GenParams(max_new_tokens=max(2, engine.turbo_steps + 2)))
        # smallest prefill bucket — short prompts must not compile on
        # hit
        run(full[:5], GenParams(max_new_tokens=2))
        # intermediate turbo variants: budget s+1 → macro-step picks
        # steps=s
        s = engine.turbo_steps // 2
        while s >= 2:
            run(full[:5], GenParams(max_new_tokens=s + 1))
            s //= 2
        # sampled path: _decode + the full-batch [B, V] sampler
        run(full[:5], GenParams(max_new_tokens=2, temperature=0.7, seed=0))
        # an all-greedy batch beside a prompt that is still prefilling
        # takes the per-token path with the [B, V] argmax (the macro-
        # step waits for the prefill): the smaller the live batch, the
        # likelier, and a run with short decode steps hit it cold
        slot, _ = engine.add_request(full[:5], GenParams(max_new_tokens=3))
        late = engine.start_request(list(full), GenParams(max_new_tokens=2))
        engine.step()
        while late not in engine.prefill_wave():
            pass
        while engine.active[slot] or engine.active[late]:
            engine.step()
        engine.release(slot)
        engine.release(late)
        runs += 2
        if engine.prefill_pack > 1:
            # packed prefill variants: every power-of-2 G bucket at the
            # full chunk width (the shapes concurrent bursts hit;
            # short-C buckets are cheap first-hit compiles). Starts are
            # traced, so one variant per (G, C) covers every start
            # combination.
            g = 2
            while g <= engine.prefill_pack and g <= engine.max_batch:
                slots = [
                    engine.start_request(list(full), GenParams(max_new_tokens=2))
                    for _ in range(g)
                ]
                runs += g
                pending = set(slots)
                while pending:
                    pending -= set(engine.prefill_wave())
                while any(engine.active[s] for s in slots):
                    engine.step()
                for s in slots:
                    engine.release(s)
                g *= 2
        engine.spec_draft = spec
        if spec:
            # the verify step. Verification is lossless whatever the
            # draft, so hand it one: a repetitive prompt drafts only if
            # the model's continuation happens to repeat, and where it
            # did not, the first live draft paid this compile
            slot, _ = engine.add_request(
                full[:5], GenParams(max_new_tokens=spec + 2)
            )
            engine._spec_step([slot], {slot: [0] * spec})
            while engine.active[slot]:
                engine.step()
            engine.release(slot)
            runs += 1
        # warmup prompts aren't real: none may linger as prefix-reuse
        # candidates (a production prompt sharing their byte pattern
        # would silently reuse warmup KV rows)
        engine.reset_prefix_cache()
        _boot_stage.set(
            runs=runs, manifest=len(engine.compile_manifest()),
        )
    with obs_boot.stage("warm_prefix_copies"):
        engine.warm_prefix_copies()
    # flight recorder steady state begins HERE: every expected compile
    # variant now exists, so any later compile is a recompile —
    # flagged loudly as the runtime complement of DTPU003 — and a
    # recompile OUTSIDE the boot-compile manifest is a warmup-coverage
    # gap
    engine.mark_flight_warm()
    logger.info(
        "warmup: %d requests compiled prefill/decode/sample%s in %.1fs",
        runs, "/verify" if spec else "", time.time() - t0,
    )


if __name__ == "__main__":
    import sys

    sys.exit(main())
