"""Engine flight recorder: per-step timeline, XLA compile accounting,
device-memory watermarks, and watchdog post-mortems.

PRs 13–14 made the *request path* observable (traces, live SLO burn);
the TPU engine itself stayed a black box: a watchdog trip, a TTFT-tail
step, or a surprise recompile left no record of what the engine was
doing. This module is the engine's black-box recorder — the standard
"why is this iteration slow" instrumentation XLA-class systems rely on
(cf. Google-Wide Profiling and the JAX/XLA persistent-compilation-cache
work in PAPERS.md):

- **Flight ring.** A bounded per-process ring of per-step flight
  records written by ``InferenceEngine.step()`` / ``prefill_wave()``
  with strictly host-side data (no device syncs — DTPU002-clean):
  step seq, phase (``prefill``/``prefill_packed``/``decode``/``spec``/
  ``turbo``), batch composition (live slots, G/C bucket, packed rows),
  host-side vs dispatch wall time (``host_s`` / ``dispatch_s``; a
  step's ``dispatch_s`` includes ``wait_s``, its time parked in the
  blocking device→host fetches), tokens emitted, KV/prefix occupancy,
  and the trace ids riding the step.
- **Compile accounting.** :func:`watch_jit` wraps every engine
  ``jax.jit`` site so first-trace/compile events are counted and timed
  per function with the causing bucket key
  (``dtpu_serve_compiles_total{fn}`` /
  ``dtpu_serve_compile_seconds{fn}`` in the ENGINE's registry — the
  wrapper is handed the registry, this module stays registry-agnostic)
  plus a ``compile`` record in the ring. A compile observed after the
  engine declared itself warm is flagged as a **steady-state
  recompile** (``recompile`` ring record, ``dtpu_serve_recompiles_
  total{fn}``, WARNING log) — the runtime complement of lint rule
  DTPU003: the power-of-two bucketing contract its noqa pragmas
  promise, watched instead of assumed.
- **Device-memory watermarks.** Best-effort ``jax`` device
  ``memory_stats()`` polled at a bounded interval into gauges and
  per-record peak fields; backends without stats (CPU jaxlib) report
  an honest ``available: false`` instead of zeros.
- **Post-mortems.** On a watchdog abort, engine exception, prefill
  failure, or deadline batch-abort, :func:`post_mortem` snapshots the
  last N flight records + the wedge attribution + compile/memory state
  into a bounded buffer, exposed with the ring via ``GET
  /debug/flight`` and the ``dtpu flight`` CLI.

Design constraints, in order (the ``faults``/``tracing`` contract):

- **Zero cost when disabled.** :func:`record` is a module-level name
  bound to :func:`_noop_record` until a recorder is installed; tests
  pin ``flight.record is flight._noop_record`` under ``DTPU_FLIGHT=0``
  and :func:`watch_jit` returns its function UNCHANGED (identity) when
  disabled at wrap time.
- **Bounded.** The ring holds ``DTPU_FLIGHT_BUFFER`` (512) records;
  post-mortems keep :data:`POSTMORTEM_KEEP` snapshots of
  :data:`POSTMORTEM_RECORDS` records each; compile events keep a
  bounded recent window.
- **Import-light.** Stdlib + ``obs.metrics`` only — no jax, no
  aiohttp at import (pinned by test like ``faults/``); the memory poll
  imports jax lazily, the way ``obs/profiling.py`` does.
- **Host-side only.** Nothing here may touch a device array: every
  record field the engine passes is a plain int/float/str/list built
  from host slot state.

Env (documented in docs/reference/server.md):

- ``DTPU_FLIGHT`` (default 1): 0/false disables the recorder entirely
  — module-level no-op rebinding, nothing is ever recorded.
- ``DTPU_FLIGHT_BUFFER`` (default 512): flight records retained.
"""

import os
import threading
import time
from collections import deque
from typing import Any, Callable, Optional

from dstack_tpu.obs.metrics import Registry
from dstack_tpu.utils.logging import get_logger

logger = get_logger("obs.flight")

__all__ = [
    "DEFAULT_BUFFER",
    "POSTMORTEM_KEEP",
    "POSTMORTEM_RECORDS",
    "FlightRecorder",
    "JitWatch",
    "watch_jit",
    "record",
    "enabled",
    "enable",
    "disable",
    "get_recorder",
    "post_mortem",
    "maybe_poll_memory",
    "health_summary",
    "debug_payload",
    "read_device_memory",
    "new_flight_registry",
    "get_flight_registry",
]

DEFAULT_BUFFER = 512
POSTMORTEM_KEEP = 16  # bounded post-mortem buffer
POSTMORTEM_RECORDS = 32  # ring records snapshotted per post-mortem
COMPILE_EVENTS_KEEP = 128  # recent compile events retained verbatim
MEM_POLL_INTERVAL_S = 0.5  # device-memory poll throttle


def _tail(seq, n) -> list:
    """Last ``n`` items as plain dict copies (``[-0:]`` would be the
    WHOLE list — 0 must mean none)."""
    n = max(0, int(n))
    if n == 0:
        return []
    return [dict(x) for x in list(seq)[-n:]]


def new_flight_registry() -> Registry:
    """Registry pre-populated with the recorder's own bookkeeping
    families (the compile/memory families live in the ENGINE's serve
    registry — ``serve/metrics.py`` — so per-replica ``/metrics``
    pages stay per-replica)."""
    r = Registry()
    r.counter(
        "dtpu_flight_records_total",
        "Flight records written to this process's bounded ring "
        "(engine steps, prefill waves, compile/recompile events, "
        "wedge markers)",
    )
    r.counter(
        "dtpu_flight_postmortems_total",
        "Post-mortem snapshots captured (watchdog aborts, engine "
        "exceptions, prefill failures, deadline batch-aborts) into "
        "the bounded post-mortem buffer",
    )
    return r


_registry: Optional[Registry] = None


def get_flight_registry() -> Registry:
    """The process-global flight registry (rendered on the OpenAI
    server's ``/metrics``)."""
    global _registry
    if _registry is None:
        _registry = new_flight_registry()
    return _registry


def read_device_memory() -> Optional[dict]:
    """Best-effort device memory stats summed across local devices →
    ``{"bytes_in_use", "peak_bytes_in_use", "bytes_limit", "devices"}``
    or None when no backend device exposes stats (CPU jaxlib returns
    ``memory_stats() is None`` — the honest ``unavailable``, never a
    fake zero). Imports jax lazily; a host-side driver query, not a
    device sync."""
    try:
        import jax

        devices = jax.devices()
    except Exception:  # noqa: BLE001 - no jax runtime = no stats
        return None
    in_use = peak = limit = 0
    seen = False
    for d in devices:
        try:
            s = d.memory_stats()
        except Exception:  # noqa: BLE001 - per-device best effort
            s = None
        if not s:
            continue
        seen = True
        in_use += int(s.get("bytes_in_use", 0))
        peak += int(s.get("peak_bytes_in_use", s.get("bytes_in_use", 0)))
        limit += int(s.get("bytes_limit", 0))
    if not seen:
        return None
    return {
        "bytes_in_use": in_use,
        "peak_bytes_in_use": peak,
        "bytes_limit": limit,
        "devices": len(devices),
    }


class FlightRecorder:
    """Bounded ring of flight records + compile/memory/post-mortem
    state.

    Thread-safe: the engine writes from a worker thread
    (``asyncio.to_thread`` dispatches) while ``/debug/flight`` and the
    watchdog read from the event loop; one lock covers everything."""

    def __init__(self, buffer: int = DEFAULT_BUFFER):
        self.buffer = max(16, int(buffer))
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=self.buffer)
        self._seq = 0
        self._postmortems: deque = deque(maxlen=POSTMORTEM_KEEP)
        # monotonic capture count: the bounded deque SATURATES at
        # POSTMORTEM_KEEP, so deltas (the soak artifact) and probe
        # signals must read this, never len(deque)
        self._postmortems_total = 0
        # compile accounting (per fn name; the causing bucket key rides
        # the per-event entries and the ring)
        self._compiles: dict = {}
        self._recompiles: dict = {}
        self._compile_seconds: dict = {}
        self._compile_events: deque = deque(maxlen=COMPILE_EVENTS_KEEP)
        # device-memory watermarks (throttled poll; running peak)
        self._mem: dict = {"available": False}
        self._mem_t = 0.0

    # -- the ring --

    def record(self, phase: str = "step", **fields) -> None:
        """Append one flight record. All values must already be
        host-side plain data (the engine's contract — never a device
        array)."""
        with self._lock:
            self._seq += 1
            entry: dict = {
                "seq": self._seq,
                "t": round(time.time(), 6),
                "phase": phase,
            }
            if self._mem.get("available"):
                # per-record watermark: the latest polled peak
                entry["mem_peak_bytes"] = self._mem.get("peak_bytes_in_use")
            for k, v in fields.items():
                if v is not None:
                    entry[k] = v
            self._ring.append(entry)
        get_flight_registry().family("dtpu_flight_records_total").inc(1)
        return None

    @property
    def seq(self) -> int:
        return self._seq

    def records(self, limit: int = 50) -> list:
        with self._lock:
            return _tail(self._ring, limit)

    # -- compile accounting --

    def note_compile(
        self,
        fn_name: str,
        key: Any,
        seconds: float,
        registry: Optional[Registry] = None,
        recompile: bool = False,
    ) -> None:
        """One observed XLA trace/compile at jit site ``fn_name``
        caused by bucket ``key`` (None for single-variant fns).
        ``seconds`` is the wall time of the triggering call — trace +
        compile + first execution, the cost the caller actually paid.
        ``recompile=True`` marks a compile the engine observed AFTER
        declaring itself warm: counted separately, logged loudly."""
        key_s = None if key is None else repr(key)
        with self._lock:
            self._compiles[fn_name] = self._compiles.get(fn_name, 0) + 1
            self._compile_seconds[fn_name] = (
                self._compile_seconds.get(fn_name, 0.0) + seconds
            )
            if recompile:
                self._recompiles[fn_name] = (
                    self._recompiles.get(fn_name, 0) + 1
                )
            self._compile_events.append({
                "t": round(time.time(), 6),
                "fn": fn_name,
                "key": key_s,
                "seconds": round(seconds, 6),
                "recompile": recompile,
            })
        self.record(
            phase="recompile" if recompile else "compile",
            fn=fn_name, key=key_s, seconds=round(seconds, 6),
        )
        if registry is not None:
            registry.family("dtpu_serve_compiles_total").inc(1, fn_name)
            registry.family("dtpu_serve_compile_seconds").observe(
                seconds, fn_name
            )
            if recompile:
                registry.family("dtpu_serve_recompiles_total").inc(
                    1, fn_name
                )
        if recompile:
            logger.warning(
                "steady-state recompile: jit site %r key=%s took %.3fs "
                "after warmup — a live TTFT/TPOT stall: either an "
                "unwarmed grid cell the warmup should cover, or a "
                "broken power-of-two bucketing contract (the runtime "
                "shape of lint rule DTPU003)",
                fn_name, key_s, seconds,
            )

    def compile_totals(self) -> dict:
        """Cumulative per-fn compile accounting — what the soak
        artifact deltas over a run."""
        with self._lock:
            return {
                "compiles": dict(self._compiles),
                "recompiles": dict(self._recompiles),
                "seconds": {
                    k: round(v, 6) for k, v in self._compile_seconds.items()
                },
            }

    def compile_events(self, limit: int = COMPILE_EVENTS_KEEP) -> list:
        with self._lock:
            return _tail(self._compile_events, limit)

    # -- device-memory watermarks --

    def maybe_poll_memory(self, registry: Optional[Registry] = None) -> dict:
        """Throttled device-memory poll (at most one driver query per
        :data:`MEM_POLL_INTERVAL_S`); updates the gauges in
        ``registry`` when stats are available and keeps the running
        peak for per-record watermark fields."""
        now = time.monotonic()
        with self._lock:
            if now - self._mem_t < MEM_POLL_INTERVAL_S:
                return dict(self._mem)
            self._mem_t = now
        stats = read_device_memory()
        with self._lock:
            if stats is None:
                self._mem = {"available": False}
            else:
                prev_peak = self._mem.get("peak_bytes_in_use", 0) or 0
                self._mem = {
                    "available": True,
                    "bytes_in_use": stats["bytes_in_use"],
                    # running high-water mark: backends that reset
                    # peak_bytes_in_use between queries still report
                    # the true process peak here
                    "peak_bytes_in_use": max(
                        prev_peak, stats["peak_bytes_in_use"]
                    ),
                    "bytes_limit": stats["bytes_limit"],
                    "devices": stats["devices"],
                }
            mem = dict(self._mem)
        if registry is not None and mem.get("available"):
            registry.family("dtpu_serve_device_memory_bytes_in_use").set(
                mem["bytes_in_use"]
            )
            registry.family("dtpu_serve_device_memory_peak_bytes").set(
                mem["peak_bytes_in_use"]
            )
        return mem

    def memory(self) -> dict:
        with self._lock:
            return dict(self._mem)

    # -- post-mortems --

    def post_mortem(
        self, reason: str, registry: Optional[Registry] = None, **ctx
    ) -> dict:
        """Snapshot the recorder's state at a failure: the last
        :data:`POSTMORTEM_RECORDS` ring records, compile accounting,
        and memory watermarks, plus the caller's context (wedge
        attribution, affected slots/traces, error text). ``registry``
        (the owning ENGINE's) additionally counts the capture into
        ``dtpu_serve_postmortems_total`` so multi-engine processes
        attribute post-mortems per replica."""
        with self._lock:
            self._postmortems_total += 1
            pm: dict = {
                "reason": reason,
                "t": round(time.time(), 6),
                "seq": self._seq,
                "records": [
                    dict(r)
                    for r in list(self._ring)[-POSTMORTEM_RECORDS:]
                ],
                "compile": {
                    "compiles": dict(self._compiles),
                    "recompiles": dict(self._recompiles),
                },
                "memory": dict(self._mem),
            }
            if ctx:
                pm["ctx"] = {
                    k: v for k, v in ctx.items() if v is not None
                }
            self._postmortems.append(pm)
        get_flight_registry().family("dtpu_flight_postmortems_total").inc(1)
        if registry is not None:
            registry.family("dtpu_serve_postmortems_total").inc(1)
        logger.warning(
            "flight post-mortem captured: %s (seq %d, %d records)",
            reason, pm["seq"], len(pm["records"]),
        )
        return pm

    def postmortems(self, limit: int = POSTMORTEM_KEEP) -> list:
        with self._lock:
            return _tail(self._postmortems, limit)

    def postmortems_total(self) -> int:
        """Monotonic capture count (never saturates, unlike the
        bounded snapshot buffer) — what deltas must read."""
        with self._lock:
            return self._postmortems_total

    # -- summaries --

    def health_summary(self) -> dict:
        """The compact block ``/health`` embeds so probes can see a
        replica mid compile storm (compiles/recompiles climbing) or
        accumulating post-mortems."""
        with self._lock:
            return {
                "enabled": True,
                "seq": self._seq,
                "compiles": int(sum(self._compiles.values())),
                "recompiles": int(sum(self._recompiles.values())),
                "postmortems": self._postmortems_total,
            }

    def snapshot(
        self, limit: int = 50, postmortems: int = POSTMORTEM_KEEP
    ) -> dict:
        with self._lock:
            fns = sorted(set(self._compiles) | set(self._recompiles))
            compile_block = {
                "fns": {
                    fn: {
                        "compiles": self._compiles.get(fn, 0),
                        "recompiles": self._recompiles.get(fn, 0),
                        "seconds": round(
                            self._compile_seconds.get(fn, 0.0), 6
                        ),
                    }
                    for fn in fns
                },
                "events": [
                    dict(e) for e in list(self._compile_events)[-20:]
                ],
            }
            return {
                "enabled": True,
                "seq": self._seq,
                "records": _tail(self._ring, limit),
                "compile": compile_block,
                "memory": dict(self._mem),
                "postmortems": _tail(self._postmortems, postmortems),
            }


class JitWatch:
    """Compile-accounting proxy around one jitted callable.

    Detects a compile on a call via the jit cache growing
    (``fn._cache_size()``, exact under current jax) with a
    first-call fallback when the introspection API is absent — the
    memoized engine grids insert one wrapper per bucket key, where
    first-call == compile by construction. ``warm`` is a zero-arg
    callable (typically reading the owning engine's warmup flag): a
    compile while it returns True is flagged as a steady-state
    recompile. ``on_compile`` is an optional
    ``(name, key, seconds, recompile)`` callback fired after the
    recorder is notified — the engine's boot-compile manifest hangs
    off it (warmup compiles populate the manifest; post-warm compiles
    are checked against it for warmup-coverage gaps)."""

    __slots__ = ("fn", "name", "key", "_registry", "_warm", "_cache_size",
                 "_calls", "_on_compile")

    def __init__(
        self,
        fn: Callable,
        name: str,
        registry: Optional[Registry] = None,
        key: Any = None,
        warm: Optional[Callable[[], bool]] = None,
        on_compile: Optional[Callable[[str, Any, float, bool], None]] = None,
    ):
        self.fn = fn
        self.name = name
        self.key = key
        self._registry = registry
        self._warm = warm
        self._cache_size = getattr(fn, "_cache_size", None)
        self._calls = 0
        self._on_compile = on_compile

    def __call__(self, *args, **kwargs):
        rec = _recorder
        if rec is None:
            return self.fn(*args, **kwargs)
        cs = self._cache_size
        before = cs() if cs is not None else None
        first = self._calls == 0
        self._calls += 1
        t0 = time.perf_counter()
        out = self.fn(*args, **kwargs)
        dt = time.perf_counter() - t0
        compiled = (cs() > before) if cs is not None else first
        if compiled:
            recompile = bool(self._warm is not None and self._warm())
            rec.note_compile(
                self.name, self.key, dt, self._registry,
                recompile=recompile,
            )
            if self._on_compile is not None:
                self._on_compile(self.name, self.key, dt, recompile)
        return out


def watch_jit(
    fn: Callable,
    name: str,
    registry: Optional[Registry] = None,
    key: Any = None,
    warm: Optional[Callable[[], bool]] = None,
    on_compile: Optional[Callable[[str, Any, float, bool], None]] = None,
) -> Callable:
    """Wrap a jitted callable for compile accounting — or return it
    UNCHANGED (identity, zero cost) when no recorder is installed at
    wrap time (engines built under ``DTPU_FLIGHT=0`` carry no wrapper
    at all — which also means no boot-compile manifest: the coverage
    gate needs the flight recorder on)."""
    if _recorder is None:
        return fn
    return JitWatch(fn, name, registry, key=key, warm=warm,
                    on_compile=on_compile)


# ---------------------------------------------------------------------------
# module-level no-op fast path (the faults.fire idiom)
# ---------------------------------------------------------------------------


def _noop_record(phase: str = "step", **fields) -> None:
    return None


# the installed recorder (None = disabled); `record` is REBOUND on
# enable so the disabled path is one no-op call — tests assert
# `flight.record is flight._noop_record` to pin the zero-cost contract
_recorder: Optional[FlightRecorder] = None
record = _noop_record


def enabled() -> bool:
    return _recorder is not None


def get_recorder() -> Optional[FlightRecorder]:
    return _recorder


def enable(buffer: int = DEFAULT_BUFFER) -> FlightRecorder:
    """Install a fresh recorder (rebinding :func:`record`) and return
    it."""
    global _recorder, record
    rec = FlightRecorder(buffer=buffer)
    _recorder = rec
    record = rec.record
    return rec


def disable() -> None:
    """Uninstall any recorder and restore the no-op fast path."""
    global _recorder, record
    _recorder = None
    record = _noop_record


def post_mortem(
    reason: str, registry: Optional[Registry] = None, **ctx
) -> Optional[dict]:
    if _recorder is None:
        return None
    return _recorder.post_mortem(reason, registry=registry, **ctx)


def maybe_poll_memory(registry: Optional[Registry] = None) -> Optional[dict]:
    if _recorder is None:
        return None
    return _recorder.maybe_poll_memory(registry)


def health_summary() -> dict:
    if _recorder is None:
        return {"enabled": False}
    return _recorder.health_summary()


def debug_payload(query) -> dict:
    """The ``GET /debug/flight`` response body (``query`` is any
    mapping of string query params: ``limit`` bounds the returned
    records, ``postmortems`` bounds the post-mortem list)."""
    if _recorder is None:
        return {"enabled": False, "records": [], "postmortems": []}
    try:
        limit = max(1, int(query.get("limit") or 50))
    except (TypeError, ValueError):
        limit = 50
    try:
        pms = max(0, int(query.get("postmortems") or POSTMORTEM_KEEP))
    except (TypeError, ValueError):
        pms = POSTMORTEM_KEEP
    return _recorder.snapshot(limit=limit, postmortems=pms)


def _env_on(name: str, default: str) -> bool:
    return os.getenv(name, default).strip().lower() not in (
        "0", "false", "no",
    )


def _install_from_env() -> None:
    """Install the recorder at import per ``DTPU_FLIGHT`` (default ON
    — the ring is bounded and a record is a handful of dict writes per
    engine STEP, not per token; ``DTPU_FLIGHT=0`` restores the no-op
    binding)."""
    if not _env_on("DTPU_FLIGHT", "1"):
        return
    try:
        buffer = int(os.getenv("DTPU_FLIGHT_BUFFER", "") or DEFAULT_BUFFER)
    except ValueError:
        buffer = DEFAULT_BUFFER
    enable(buffer=buffer)


_install_from_env()
