"""The serving round on one clock: a traced slice's device idle time,
charged by overlap to the program's host spans.

A profile's device timeline is converted to the host clock by the
profiler, and that conversion can be off by a large share of a round's
idle gap: a device program can appear to start before the host call that
launched it.  :func:`align` puts the device back behind its launches;
:func:`idle_by_stage` then splits every idle gap on the chip over the
program spans (``obs.trace.span``: ``sched.*``, ``engine.*``, ``flush``,
``drain``; and ``bench.*``, where the driving loop spans its own work)
open on the driving thread while it lasted, the innermost span taking
each instant; :func:`scope_time` sums the device
time of the operations under one named scope.  :func:`reduce` does all
three, and :func:`metrics` reads the round's numbers from it.

The trace is the dict ``trace_reduce.load`` makes, which a traced run
keeps with ``bench/run.py --keep-trace``; :func:`load` adds the
operations' scope paths to it from an ``.xplane.pb``.
``trace_reduce.reduce`` and the metrics that read it are left as they
are.  From the root of a checkout::

    python3 -m bench.round_trace <trace.json.gz | trace.xplane.pb>
"""
from __future__ import annotations

import argparse
import bisect
import gzip
import json
import pathlib
import re
import statistics
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from bench import trace_reduce as tr

#: host event of a jitted call (its device program is ``jit_<fn>``)
LAUNCH = re.compile(r"^PjitFunction\((.+)\)$")
#: the program spans that own idle time (``obs.trace.span`` names)
STAGE_PREFIXES = ("sched.", "engine.", "bench.", "flush", "drain",
                  "ticket-wait")
#: the driving loop's sleep for want of work, kept apart where spanned
IDLE_WAIT = "bench.idle_wait"
UNTRACED = "untraced"
#: the decode program: one execution per decode round
DECODE = "decode_fn"
#: a launch and its device program lie at most this far apart (ns)
MAX_PAIR_NS = 10e6
MS = 1e-6          # ns -> ms
#: trace key of the operations' scope paths, per device: ``{"names":
#: [path, ...], "index": [i into names or -1, one per XLA Ops event]}``
OP_SCOPES = "op_scopes"
#: the operation stat that holds its scope path (``jit(f)/.../dot:``); a
#: TPU trace keeps it in the operation's event metadata, which
#: ``ProfileData`` does not expose, so :func:`op_scopes` reads the file
SCOPE_STAT = "tf_op"
#: the named scope of the decode attention (``models/paged.py``)
DECODE_ATTENTION = "decode_attention"


def _varint(buf, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf, lo: int, hi: int) -> Iterator[Tuple[int, object]]:
    """(field number, value) of the protobuf message in ``buf[lo:hi]``: an
    int for a varint, (start, end) for a length-delimited field."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield field, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def op_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """Per device plane, ``{operation event name: scope path}`` from the
    :data:`SCOPE_STAT` of each operation's event metadata in the
    ``.xplane.pb`` (``XSpace.planes``: ``name`` 2, ``event_metadata`` 4,
    ``stat_metadata`` 5; ``XEventMetadata``: ``name`` 2, ``stats`` 5;
    ``XStat``: ``metadata_id`` 1, ``str_value`` 5, ``ref_value`` 7)."""
    buf = memoryview(pathlib.Path(path).read_bytes())
    out: Dict[str, Dict[str, str]] = {}
    for field, plane in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(buf, *plane):
            if f == 2:
                name = _text(buf, v)
            elif f == 4:
                events.append(v)
            elif f == 5:
                md = dict(_fields(buf, *dict(_fields(buf, *v)).get(2, (0, 0))))
                stat_names[md.get(1, 0)] = _text(buf, md[2]) if 2 in md \
                    else ""
        if not tr.DEVICE_PLANE.match(name):
            continue
        ids = {k for k, n in stat_names.items() if n == SCOPE_STAT}
        scopes: Dict[str, str] = {}
        for v in events:
            md = list(_fields(buf, *dict(_fields(buf, *v)).get(2, (0, 0))))
            ev = next((_text(buf, x) for f, x in md if f == 2), "")
            for f, x in md:
                st = dict(_fields(buf, *x)) if f == 5 else {}
                if st.get(1) in ids:
                    scopes[ev] = _text(buf, st[5]) if 5 in st \
                        else stat_names.get(st.get(7), "")
        out[name] = scopes
    return out


def load(path: str, devices: Optional[Sequence[int]] = None) -> Dict:
    """``trace_reduce.load`` of an ``.xplane.pb``, and where operations
    carry scope paths, ``trace[OP_SCOPES][id]``: one per ``XLA Ops``
    event of the device, in the order of that line's events."""
    from jax.profiler import ProfileData
    trace = tr.load(path, devices)
    scopes = op_scopes(path)
    for plane in ProfileData.from_file(path).planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        dev = str(int(m.group(1))) if m else None
        if dev not in trace["devices"]:
            continue
        by_name = scopes.get(plane.name, {})
        for line in plane.lines:
            if line.name != tr.OPS:
                continue
            names: Dict[str, int] = {}
            index = []
            for e in line.events:
                path_ = by_name.get(e.name)
                index.append(-1 if path_ is None
                             else names.setdefault(path_, len(names)))
            if names:
                trace.setdefault(OP_SCOPES, {})[dev] = {
                    "names": list(names), "index": index}
    return trace


def stage_name(event: str) -> str:
    """A span's name without the ``#key=value#`` metadata suffix a
    ``TraceAnnotation`` with labels may give its event."""
    return event.split("#", 1)[0]


def _is_stage(name: str) -> bool:
    return name != tr.WINDOW_SPAN and name.startswith(STAGE_PREFIXES)


def _launches(trace: Dict, thread: Optional[str]) -> List[Tuple[str, float]]:
    """(device program, host start) of every jitted call on ``thread``;
    a call nested in another of the same function (the profiler records
    both) counts once."""
    out: List[Tuple[str, float]] = []
    ends: Dict[str, float] = {}
    for name, s, d, th in sorted(trace["host"], key=lambda h: h[1]):
        m = LAUNCH.match(name)
        if m is None or (thread is not None and th != thread):
            continue
        prog = "jit_" + m.group(1)
        if s < ends.get(prog, -1.0):
            continue
        ends[prog] = s + d
        out.append((prog, s))
    return out


def launch_pairs(trace: Dict) -> List[Tuple[float, float]]:
    """(host call start, device program start) of every launch on the
    driving thread, paired in order with the executions of its program:
    each call takes the first execution not yet taken that starts no
    more than :data:`MAX_PAIR_NS` before it (a program can queue behind
    another and start long after its call, never long before it).  On
    every device of the trace."""
    calls = _launches(trace, tr.driving_thread(trace))
    pairs = []
    for lines in trace["devices"].values():
        starts: Dict[str, List[float]] = {}
        for name, s, _ in lines.get(tr.MODULES, []):
            starts.setdefault(tr.program_name(name), []).append(s)
        for cand in starts.values():
            cand.sort()
        nxt: Dict[str, int] = {}
        for prog, h in calls:
            cand = starts.get(prog, [])
            j = bisect.bisect_left(cand, h - MAX_PAIR_NS, lo=nxt.get(prog, 0))
            if j < len(cand):
                pairs.append((h, cand[j]))
                nxt[prog] = j + 1
    return pairs


def align(trace: Dict) -> Tuple[Dict, Dict]:
    """The trace with every device event shifted by the smallest offset
    at which no paired device program starts before its host call (0
    when none does), and ``{"offset_ms", "pairs"}``."""
    pairs = launch_pairs(trace)
    off = max([0.0] + [h - d for h, d in pairs])
    devices = {dev: {ln: ([[e[0], e[1] + off, *e[2:]] for e in evs]
                          if ln in (tr.MODULES, tr.OPS) else evs)
                     for ln, evs in lines.items()}
               for dev, lines in trace["devices"].items()}
    return ({**trace, "devices": devices},
            {"offset_ms": off * MS, "pairs": len(pairs)})


def _segments(host: List, lo: float, hi: float
              ) -> List[Tuple[float, float, str]]:
    """[lo, hi) cut where program spans open and close, each piece named
    after the innermost span open over it (the latest opened), or
    :data:`UNTRACED`."""
    spans = sorted((s, s + d, stage_name(n)) for n, s, d, _ in host
                   if _is_stage(stage_name(n)) and s + d > lo and s < hi)
    cuts = sorted({lo, hi} | {t for s, e, _ in spans for t in (s, e)
                              if lo < t < hi})
    out, active, k = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(spans) and spans[k][0] <= a:
            active.append(spans[k])
            k += 1
        active = [sp for sp in active if sp[1] > a]
        name = max(active, key=lambda sp: (sp[0], -sp[1]))[2] \
            if active else UNTRACED
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def _busy(lines: Dict, lo: float, hi: float) -> List[Tuple[float, float]]:
    source = lines.get(tr.OPS) or lines.get(tr.MODULES, [])
    return tr._union([(max(e[1], lo), min(e[1] + e[2], hi))
                      for e in source if e[1] + e[2] > lo and e[1] < hi])


def _gaps(busy: List[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    out, t = [], lo
    for a, b in busy + [(hi, hi)]:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    return out


def _charge(gaps, segs) -> List[Tuple[float, float, str]]:
    """Each gap cut along the segments it overlaps."""
    out, j = [], 0
    for g0, g1 in gaps:
        while j < len(segs) and segs[j][1] <= g0:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < g1:
            a, b = max(segs[k][0], g0), min(segs[k][1], g1)
            if b > a:
                out.append((a, b, segs[k][2]))
            k += 1
    return out


def idle_by_stage(trace: Dict) -> Dict:
    """Device idle time over the traced slice, split by overlap over the
    program spans on the driving thread: ``by_stage`` {stage: seconds}
    (``bench.idle_wait`` and :data:`UNTRACED` among them), and per decode
    round (from one decode program's start to the next's) the median of
    each stage's idle ``round_ms``, and of the idle under any program
    span but ``bench.idle_wait``, ``round_host_ms``.  Averaged over the
    trace's devices."""
    lo, hi = tr.window_of(trace)
    thread = tr.driving_thread(trace)
    host = [h for h in trace["host"] if thread is None or h[3] == thread]
    segs = _segments(host, lo, hi)
    devs = trace["devices"]
    n_dev = max(len(devs), 1)
    by_stage: Dict[str, float] = {}
    per_round: List[Dict[str, float]] = []
    for lines in devs.values():
        pieces = _charge(_gaps(_busy(lines, lo, hi), lo, hi), segs)
        ends = [p[1] for p in pieces]
        for a, b, name in pieces:
            by_stage[name] = by_stage.get(name, 0.0) + (b - a) * 1e-9 / n_dev
        starts = sorted(s for n, s, _ in lines.get(tr.MODULES, [])
                        if DECODE in n and lo <= s < hi)
        for r0, r1 in zip(starts, starts[1:]):
            i = bisect.bisect_right(ends, r0)
            got: Dict[str, float] = {}
            for a, b, name in pieces[i:]:
                if a >= r1:
                    break
                a, b = max(a, r0), min(b, r1)
                if b > a:
                    got[name] = got.get(name, 0.0) + (b - a) * MS
            per_round.append(got)
    names = {n for r in per_round for n in r}
    host_ms = [sum(v for n, v in r.items() if n not in (IDLE_WAIT, UNTRACED))
               for r in per_round]
    return {
        "window_s": (hi - lo) * 1e-9,
        "idle_s": sum(by_stage.values()),
        "by_stage": by_stage,
        "rounds": len(per_round),
        "round_ms": {n: statistics.median(r.get(n, 0.0) for r in per_round)
                     for n in sorted(names)},
        "round_host_ms": statistics.median(host_ms) if host_ms else None,
        "spans": sorted({stage_name(h[0]) for h in host
                         if _is_stage(stage_name(h[0]))}),
    }


def scope_time(trace: Dict, scope: str, program: str = DECODE
               ) -> Optional[Dict]:
    """Device time of the operations under named scope ``scope`` inside
    executions of ``program``: ``{"count", "device_s"}`` (count = the
    executions), the union of those operations' intervals so nested
    operations count once.  None when the trace carries no scope paths
    (:data:`OP_SCOPES`) or no such operation."""
    lo, hi = tr.window_of(trace)
    count, secs, found = 0, 0.0, False
    for dev, lines in trace["devices"].items():
        scopes = trace.get(OP_SCOPES, {}).get(dev)
        if not scopes:
            continue
        names, index = scopes["names"], scopes["index"]
        hit = {i for i, n in enumerate(names) if scope in n}
        execs = [(s, s + d) for n, s, d in lines.get(tr.MODULES, [])
                 if program in n and s >= lo and s + d <= hi]
        if not hit or not execs:
            continue
        ops = sorted((e[1], e[1] + e[2])
                     for e, k in zip(lines[tr.OPS], index) if k in hit)
        merged = tr._union(ops)
        for a, b in execs:
            i = bisect.bisect_left(merged, (a, a))
            while i > 0 and merged[i - 1][1] > a:
                i -= 1
            for s, e in merged[i:]:
                if s >= b:
                    break
                secs += (min(e, b) - max(s, a)) * 1e-9
        count += len(execs)
        found = True
    return {"count": count, "device_s": secs} if found else None


def reduce(trace: Dict) -> Dict:
    """The per-layer reduction of the round: :func:`align`, then
    :func:`idle_by_stage` and the decode attention's :func:`scope_time`
    on the aligned trace, with the offset and the pairs that set it."""
    aligned, clock = align(trace)
    return {**clock, **idle_by_stage(aligned),
            "decode_attention": scope_time(aligned, DECODE_ATTENTION)}


def metrics(st: Dict) -> Dict[str, Optional[float]]:
    """The round's numbers from a :func:`reduce`, in ms, each None where
    the trace has nothing to read: ``round_host_ms`` (idle per decode
    round under any program span but ``bench.idle_wait``, median),
    ``logits_fetch_ms`` and ``table_upload_ms`` (idle per decode round
    under ``engine.logits_fetch`` and ``engine.table_upload``, median) --
    these three only where the program spans ``sched.step`` -- and
    ``decode_attn_ms`` (device time per decode program under the
    :data:`DECODE_ATTENTION` scope)."""
    spanned = "sched.step" in st["spans"] and st["rounds"] > 0
    da = st["decode_attention"]
    return {
        "round_host_ms": st["round_host_ms"] if spanned else None,
        "logits_fetch_ms": (st["round_ms"].get("engine.logits_fetch", 0.0)
                            if spanned else None),
        "table_upload_ms": (st["round_ms"].get("engine.table_upload", 0.0)
                            if spanned else None),
        "decode_attn_ms": (da["device_s"] / da["count"] * 1e3
                           if da and da["count"] else None),
    }


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="a trace kept by bench/run.py "
                    "--keep-trace (.json.gz), or an .xplane.pb")
    args = ap.parse_args(argv)
    if args.trace.endswith(".xplane.pb"):
        trace = load(args.trace)
    else:
        with gzip.open(args.trace, "rt") as f:
            trace = json.load(f)
    st = reduce(trace)
    print(f"[bench] host-device offset {st['offset_ms']:.4f} ms from "
          f"{st['pairs']} launches")
    print(f"[bench] idle {st['idle_s']:.4f} s of {st['window_s']:.4f} s by "
          "stage: " + json.dumps(dict(sorted(st["by_stage"].items(),
                                             key=lambda x: -x[1]))))
    print(f"[bench] per decode round ({st['rounds']} rounds), median idle "
          "ms: " + json.dumps(st["round_ms"]))
    print(json.dumps(metrics(st)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
