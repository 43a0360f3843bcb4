"""From a profiler trace to the numbers the per-layer metrics read.

:func:`load` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain dict (kept as JSON for the test fixture); :func:`reduce` computes,
over a window:

* device busy time, the union of the intervals in which an operation runs
  on each chip (averaged over chips), and the idle share;
* device time and executions per jitted program (the ``XLA Modules``
  line, program names stripped of their instance suffix), and per
  operation;
* every idle gap on the device, attributed to the innermost host span
  open at its midpoint on the thread that drives the chip (the one that
  opened the ``bench.window`` span): the benchmark's and the program's
  ``TraceAnnotation``s, and JAX's own (``np.asarray(jax.Array)`` is a
  device-to-host copy the thread waits on).
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES, OPS = "XLA Modules", "XLA Ops"
#: host span that brackets the traced window (the harness opens it)
WINDOW_SPAN = "bench.window"
NO_SPAN = "no host span"


def program_name(event: str) -> str:
    """``jit__decode_fn(123)`` -> ``jit__decode_fn``."""
    return re.sub(r"\(\d+\)$", "", event).strip()


def op_name(event: str) -> str:
    """An operation event carries its HLO instruction's text; keep its
    name (``%fusion.12 = ...`` -> ``%fusion.12``)."""
    return event.split(" = ", 1)[0]


def load(path: str, devices: Optional[Sequence[int]] = None) -> Dict:
    """The trace as ``{"devices": {id: {line: [[name, start_ns, dur_ns],
    ...]}}, "host": [[name, start_ns, dur_ns, thread], ...]}`` keeping
    only the device planes of ``devices`` (all TPU planes when None)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if devices is not None and dev not in devices:
                continue
            lines = {}
            for line in plane.lines:
                if line.name in (MODULES, OPS):
                    lines[line.name] = [[op_name(e.name), float(e.start_ns),
                                         float(e.duration_ns)]
                                        for e in line.events]
            out["devices"][str(dev)] = lines
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("$"):
                        continue        # python-tracer frames, if any
                    out["host"].append([e.name, float(e.start_ns),
                                        float(e.duration_ns), line.name])
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def window_of(trace: Dict) -> Tuple[float, float]:
    """The ``bench.window`` host span, else the extent of device work."""
    spans = [(s, s + d) for n, s, d, _ in trace["host"] if n == WINDOW_SPAN]
    if spans:
        return spans[0]
    evs = [(s, s + d) for lines in trace["devices"].values()
           for line in lines.values() for _, s, d in line]
    return min(a for a, _ in evs), max(b for _, b in evs)


def driving_thread(trace: Dict) -> Optional[str]:
    """The host thread that opened the ``bench.window`` span."""
    for name, _, _, thread in trace["host"]:
        if name == WINDOW_SPAN:
            return thread
    return None


def _span_at(host: List, t: float) -> str:
    """Innermost host span open at ``t`` (the shortest one containing
    it), other than the window bracket."""
    best, best_d = NO_SPAN, None
    for name, s, d, _ in host:
        if name == WINDOW_SPAN or not s <= t <= s + d:
            continue
        if best_d is None or d < best_d:
            best, best_d = name, d
    return best


def reduce(trace: Dict, window: Optional[Tuple[float, float]] = None
           ) -> Dict:
    lo, hi = window if window is not None else window_of(trace)
    win = hi - lo
    programs: Dict[str, Dict[str, float]] = {}
    ops: Dict[str, float] = {}
    busy_total = 0.0
    gaps: List[Tuple[float, float, str]] = []
    thread = driving_thread(trace)
    host = [h for h in trace["host"] if thread is None or h[3] == thread]
    devs = trace["devices"]
    for dev, lines in sorted(devs.items()):
        for name, s, d in lines.get(MODULES, []):
            a, b = _clip(s, s + d, lo, hi)
            if b <= a:
                continue
            p = programs.setdefault(program_name(name),
                                    {"count": 0, "device_s": 0.0})
            p["count"] += 1
            p["device_s"] += (b - a) * 1e-9
        source = lines.get(OPS) or lines.get(MODULES, [])
        iv = []
        for name, s, d in source:
            a, b = _clip(s, s + d, lo, hi)
            if b > a:
                iv.append((a, b))
                ops[name] = ops.get(name, 0.0) + (b - a) * 1e-9
        merged = _union(iv)
        busy_total += sum(b - a for a, b in merged)
        t = lo
        for a, b in merged + [(hi, hi)]:
            if a > t:
                gaps.append((t, a - t, _span_at(host, (t + a) / 2)))
            t = max(t, b)
    n_dev = max(len(devs), 1)
    busy = busy_total / n_dev * 1e-9
    idle_by_span: Dict[str, float] = {}
    for _, d, name in gaps:
        idle_by_span[name] = idle_by_span.get(name, 0.0) + d * 1e-9 / n_dev
    return {
        "window_s": win * 1e-9,
        "busy_s": busy,
        "devices": n_dev,
        "programs": programs,
        "ops": ops,
        "gaps": [(s, d * 1e-9, name) for s, d, name in gaps],
        "idle_by_span": idle_by_span,
    }


def program_time(red: Dict, needle: str) -> Tuple[int, float]:
    """(executions, device seconds) of the programs whose name holds
    ``needle``."""
    n, t = 0, 0.0
    for name, p in red["programs"].items():
        if needle in name:
            n += p["count"]
            t += p["device_s"]
    return n, t


def breakdown(red: Dict, top: int = 10) -> Dict[str, List]:
    """The programs that took the most device time and the host spans the
    most idle time fell in, at most ``top`` of each."""
    progs = sorted(((n, p["device_s"]) for n, p in red["programs"].items()),
                   key=lambda x: -x[1])[:top]
    idle = sorted(red["idle_by_span"].items(), key=lambda x: -x[1])[:top]
    return {"device_ops": [[n, s] for n, s in progs],
            "idle_gaps": [[n, s] for n, s in idle]}
