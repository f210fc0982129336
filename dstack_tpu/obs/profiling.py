"""JAX profiler capture: start/stop a trace into a directory.

One guarded wrapper shared by the serve server's
``/debug/profiler/start|stop`` endpoints and any other process that
wants on-demand traces. Captures are gated behind
``DTPU_PROFILER_DIR`` (settings flag): unset means the endpoints are
not even registered — a production server must not expose an
unauthenticated knob that writes multi-GB traces to disk.

jax is imported lazily so control-plane-only deployments never pay
the import.

:func:`span` puts the program's host phases (engine step, prefill
dispatch, scheduler tick, detokenize, stream write) on the capture's
clock: while a capture started HERE is running it is a
``jax.profiler.TraceAnnotation`` on ``/host:CPU`` of the same
``.xplane.pb``; otherwise it is :func:`_noop_span` (the ``faults.fire``
idiom: the name is rebound by start/stop, so call it as
``profiling.span(...)``). Timing for ``/metrics`` is the caller's own
``perf_counter`` pair around the same interval, always on.
"""

import contextlib
import os
import threading
from typing import Optional

_lock = threading.Lock()
_active_dir: Optional[str] = None
_NULL_SPAN = contextlib.nullcontext()


def _noop_span(name: str, **kw):
    return _NULL_SPAN


span = _noop_span


def profiler_dir() -> Optional[str]:
    """The configured capture directory, or None when disabled."""
    return os.environ.get("DTPU_PROFILER_DIR") or None


def start_trace(trace_dir: Optional[str] = None) -> dict:
    """Begin a capture; returns {"tracing": True, "dir": ...}.
    Raises RuntimeError when a capture is already running."""
    global _active_dir, span
    d = trace_dir or profiler_dir()
    if not d:
        raise RuntimeError("profiler disabled (set DTPU_PROFILER_DIR)")
    import jax

    with _lock:
        if _active_dir is not None:
            raise RuntimeError(f"trace already running into {_active_dir}")
        os.makedirs(d, exist_ok=True)
        jax.profiler.start_trace(d)
        _active_dir = d
        span = jax.profiler.TraceAnnotation
    return {"tracing": True, "dir": d}


def stop_trace() -> dict:
    """End the capture; returns {"tracing": False, "dir": ...}.
    Raises RuntimeError when no capture is running."""
    global _active_dir, span
    import jax

    with _lock:
        if _active_dir is None:
            raise RuntimeError("no trace running")
        d = _active_dir
        span = _noop_span  # before the (long) stop: no new annotations
        jax.profiler.stop_trace()
        _active_dir = None
    return {"tracing": False, "dir": d}


def is_tracing() -> bool:
    return _active_dir is not None
