"""Profiler capture (``.xplane.pb``) → device busy/idle, per-program
time, top operations, and idle gaps by what the host was doing.

What the planes are (looked at by hand on a v5e capture, PERF.md §3):
``/device:TPU:<n>`` is one chip; its line ``XLA Ops`` holds one event
per executed HLO operation, named by the whole HLO line (``%fusion.3 =
bf16[...] fusion(...)``; their union is "busy"), ``XLA Modules`` one
event per executed program, named ``jit_<function>(<fingerprint>)``;
``Async XLA Ops`` (copy-start .. copy-done spans) and ``TC Overlay`` are
not counted. ``/host:CPU`` holds one line per host thread: ``python3``
with the Python tracer's ``$file:line function`` events and any
``TraceAnnotation``, ``main/<tid>`` and others with the runtime's own
(``PJRT_LoadedExecutable_Execute`` ...). The other planes (``#Chip0 Host
Interface``, ``#Chip0 Misc``, ``/host:metadata``, ``Task Environment``,
``/device:CUSTOM:Megascale Trace``) hold nothing this reads.

The traced window runs from the end of the host's ``start_trace`` call
to the start of its ``stop_trace`` call (both are Python events of the
capture itself); starting and stopping the profiler is not the
program's idle time.

Only ``jax.profiler.ProfileData`` is needed to read the file.
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> dict:
    """→ ``{plane name: {line name: [(name, start_ns, duration_ns)]}}``."""
    from jax.profiler import ProfileData

    planes = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events
            )
    return planes


def union(intervals) -> list:
    """Sorted, merged ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def program_name(event_name: str) -> str:
    """``jit_decode_step(1234567)`` → ``decode_step``."""
    name = re.sub(r"\(\d+\)$", "", event_name)
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """``%fusion.3 = bf16[..] fusion(..)`` → ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def traced_window(planes: dict):
    """→ (t0, t1) in ns: the span of all events, without the host's own
    ``start_trace`` / ``stop_trace`` calls at its ends (the device's and
    the host's clocks can differ by a millisecond or so in a capture, so
    device events are never cut by host times)."""
    edge = lambda n: n.endswith(" start_trace") or n.endswith(" stop_trace")
    spans, start_end, stop_start = [], None, None
    for plane, lines in planes.items():
        for evs in lines.values():
            for n, s, d in evs:
                if plane == HOST_PLANE and edge(n):
                    if n.endswith(" start_trace"):
                        start_end = max(start_end or 0.0, s + d)
                    else:
                        stop_start = min(stop_start or float("inf"), s)
                    continue
                spans.append((s, s + d))
    t0, t1 = min(s for s, _ in spans), max(e for _, e in spans)
    dev = [
        (s, s + d) for p, lines in planes.items() if DEVICE_PLANE.match(p)
        for _, s, d in lines.get(OPS_LINE, [])
    ]
    if start_end is not None:
        t0 = max(t0, min([start_end] + [s for s, _ in dev]))
    if stop_start is not None:
        t1 = min(t1, max([stop_start] + [e for _, e in dev]))
    return t0, t1


def _top(table: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def _host_threads(planes: dict) -> list:
    """Per host thread: (starts, ends, names) sorted by start."""
    import numpy as np

    out = []
    for events in planes.get(HOST_PLANE, {}).values():
        evs = sorted((s, s + d, n) for n, s, d in events if d > 0)
        if evs:
            out.append((
                np.array([e[0] for e in evs]), np.array([e[1] for e in evs]),
                [e[2] for e in evs],
            ))
    return out


def attribute_gaps(gaps: list, threads: list, most: int = 5000, back: int = 256) -> dict:
    """Idle seconds by the innermost host event (shortest, over all
    threads) that covers the gap's midpoint; ``"(no host event)"`` where
    none does. Only the ``most`` longest gaps are looked up, the rest is
    summed as ``"(short gaps)"``."""
    import numpy as np

    by = {}
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])
    for s, e in gaps[most:]:
        by["(short gaps)"] = by.get("(short gaps)", 0.0) + (e - s) / 1e9
    for s, e in gaps[:most]:
        mid = (s + e) / 2
        best = None
        for starts, ends, names in threads:
            i = int(np.searchsorted(starts, mid, side="right")) - 1
            # events of one thread nest: walk back past finished
            # siblings to the innermost one still open at ``mid``
            for j in range(i, max(-1, i - back), -1):
                if ends[j] >= mid:
                    if best is None or ends[j] - starts[j] < best[0]:
                        best = (ends[j] - starts[j], names[j])
                    break
        name = best[1] if best else "(no host event)"
        by[name] = by.get(name, 0.0) + (e - s) / 1e9
    return by


def reduce_planes(planes: dict, chips: int = 1, min_gap_ns: float = 20_000.0) -> dict:
    """The reduction proper, on :func:`load`'s output."""
    devices = sorted(
        (int(m.group(1)), name) for name in planes if (m := DEVICE_PLANE.match(name))
    )[:chips]
    if not devices:
        raise ValueError(f"no device plane among {sorted(planes)}")
    t0, t1 = traced_window(planes)
    window_s = (t1 - t0) / 1e9
    host = _host_threads(planes)
    busy, ops, programs, gaps_by = [], {}, {}, {}
    program_calls = {}
    for _, name in devices:
        lines = planes[name]
        op_events = [
            (n, max(s, t0), min(s + d, t1) - max(s, t0))
            for n, s, d in lines.get(OPS_LINE, []) if s < t1 and s + d > t0
        ]
        merged = union((s, s + d) for _, s, d in op_events if d > 0)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for n, _, d in op_events:
            ops[op_name(n)] = ops.get(op_name(n), 0.0) + d / 1e9
        for n, s, d in lines.get(MODULES_LINE, []):
            if not (s < t1 and s + d > t0):
                continue
            p = program_name(n)
            programs[p] = programs.get(p, 0.0) + d / 1e9
            program_calls[p] = program_calls.get(p, 0) + 1
        edges = [t0] + [x for s, e in merged for x in (s, e)] + [t1]
        gaps = [
            (edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] - edges[i] >= min_gap_ns
        ]
        for k, v in attribute_gaps(gaps, host).items():
            gaps_by[k] = gaps_by.get(k, 0.0) + v
    n = len(devices)
    return {
        "chips": n,
        "window_s": window_s,
        "busy_s": sum(busy) / n,
        "programs_s": {k: v / n for k, v in programs.items()},
        "program_calls": program_calls,
        "breakdown": {
            "device_ops": _top({k: v / n for k, v in ops.items()}),
            "idle_gaps": _top({k: v / n for k, v in gaps_by.items()}),
        },
    }


def reduce_dir(trace_dir: str, chips: int = 1) -> dict:
    return reduce_planes(load(find_xplane(trace_dir)), chips)
