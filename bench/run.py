"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run:

1. builds the cell's ``ServingEngine`` (weights made on the device from
   ``--seed`` by ``bench/weights.py``, in the configuration's weight
   layout) and wraps it in a ``RequestScheduler`` with the mix's tenants;
2. warms every shape the window will use: one request of each prompt
   length, every drain bucket, then ``warmup_s`` of the cell's own traffic;
3. drives the window with the traffic for ``--seconds`` through
   ``RequestScheduler.step``, timing every request on the host clock;
4. with ``--trace 1``, traces the window's last seconds and reduces them to the
   per-layer metrics (``bench/metrics``); without, reports the end-to-end
   metrics;
5. frees the engine and checks a seeded sample of the finished requests
   against the plain reference (``logit_gaps`` of the configuration's
   module, ``bench/references/<reference>.py``): ``correct`` when
   every number compared (``max_logit_gap``, the widest gap by which a
   served token's logit lies below the reference's best, and any other
   the configuration's ``correct.limits`` names) is within its limit.

The last line of standard output is one JSON object; the numbers compared
are the last lines of standard error.  Exits 2, printing no result, when
JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is timed from the process start

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import cost, spec, stats, weights  # noqa: E402

#: seconds at the end of the window the ``--trace 1`` run traces (device
#: traces of whole windows are too large to reduce in a run's time)
TRACE_SECONDS = 8.0
#: where traces are written, inside the checkout
OUT_DIR = ROOT / ".bench_out"
#: token-stream offset of the correctness sample draw
SAMPLE_STREAM = 17
#: routing margins (router-logit RMS) at which calibration reads MoE gaps
MARGINS = (0.0, 0.005, 0.01, 0.02, 0.03, 0.05, 0.1)
#: backend compiles of this process (one listener, counted per run)
COMPILES = {"n": 0, "s": 0.0, "window": 0, "in_window": False}
_LISTENER: List = []


def now() -> float:
    return time.perf_counter()


def count_compiles() -> None:
    """Start counting backend compiles from 0 (the listener is registered
    once per process)."""
    import jax
    if not _LISTENER:
        def on_compile(name, secs, **kw):
            if name == "/jax/core/compile/backend_compile_duration":
                COMPILES["n"] += 1
                COMPILES["s"] += secs
                COMPILES["window"] += COMPILES["in_window"]

        jax.monitoring.register_event_duration_secs_listener(on_compile)
        _LISTENER.append(on_compile)
    COMPILES.update(n=0, s=0.0, window=0, in_window=False)


class Failed(RuntimeError):
    """The run cannot report a result (no chip, too few chips)."""


# --------------------------------------------------------------------------
# the system under test
# --------------------------------------------------------------------------
def program_config(m: dict):
    """The program's ModelConfig for configuration file ``m``: the
    registry entry ``arch`` with ``overrides``, checked against every
    number the file states (``program_checks`` of its module), so the
    file is what runs."""
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(m["arch"]), **m["overrides"])
    want = spec.reference(m).program_checks(m)
    bad = {k: (getattr(cfg, k), v) for k, v in want.items()
           if getattr(cfg, k) != v}
    if bad:
        raise ValueError(f"{m['name']}: program config differs from the "
                         f"file (program, file): {bad}")
    return cfg


def build_engine(m: dict, seed: int):
    """The cell's ServingEngine over weights made from ``seed``."""
    import jax
    from repro.launch.serve import ServingEngine
    from repro.models import build_model, split_params
    cfg = program_config(m)
    params = weights.make_params(m, seed)
    want = jax.eval_shape(
        lambda k: split_params(build_model(cfg).init_params(k))[0],
        jax.random.key(0))
    got_s = jax.tree_util.tree_structure(params)
    if got_s != jax.tree_util.tree_structure(want) or any(
            a.shape != b.shape for a, b in zip(
                jax.tree_util.tree_leaves(params),
                jax.tree_util.tree_leaves(want))):
        raise ValueError(f"the weight layout of bench/references/"
                         f"{m['reference']}.py differs from the program's "
                         "parameter layout")
    e = m["engine"]
    return ServingEngine(cfg, params, max_seqs=e["max_seqs"],
                         max_blocks_per_seq=e["max_blocks_per_seq"],
                         max_admit_pages=e["max_admit_pages"],
                         spill_pages=e["spill_pages"],
                         dedup_admit=e["dedup_admit"],
                         adaptive_ring=e["adaptive_ring"])


def warm_drain(eng) -> None:
    """Compile the drain at every table bucket: an all-NOP table of each
    bucket size through the engine's own dispatch (it moves nothing)."""
    import jax.numpy as jnp
    from repro.core.cmdqueue import get_buckets
    from repro.core.opcodes import OP_NOP
    from repro.kernels import ops as kops
    rce = eng.engine
    for b in get_buckets():
        table = np.full((b, 3), OP_NOP, np.int32)
        new = kops.fused_dispatch(tuple(rce.pools.values()),
                                  rce._get_zero_blocks(), jnp.asarray(table),
                                  block_axis=rce.block_axis,
                                  primary=rce.group.primary,
                                  overlap=rce.overlap)
        for name, arr in zip(list(rce.pools), new):
            rce.pools[name] = arr


# --------------------------------------------------------------------------
# the driving loop
# --------------------------------------------------------------------------
class TrafficLoop:
    """Feeds a traffic source into a RequestScheduler and records, on the
    host clock, when every request was due, submitted, admitted and given
    each token."""

    def __init__(self, sched, source, m: dict, page: int):
        self.sched = sched
        self.source = source
        self.m = m
        self.page = page
        self.req: Dict[int, Dict] = {}      # rid -> record
        self.active: Dict[int, int] = {}    # rid -> tokens seen
        self.rounds: List[Dict] = []        # per step: t_a, t_b, flops
        self.sample_fn = None
        self.t0 = now()

    def submit(self, r: Dict, t: float) -> None:
        rid = self.sched.submit(r["tenant"], r["prompt"],
                                max_new_tokens=r["max_new"])
        self.req[rid] = {"key": r.get("key"), "tenant": r["tenant"],
                         "prompt": r["prompt"], "max_new": r["max_new"],
                         "due": r["due"], "submitted": t, "admitted": None,
                         "first_token": None, "times": [], "done": None}
        self.active[rid] = 0

    def step(self) -> None:
        t_a = now() - self.t0
        rep = self.sched.step(sample_fn=self.sample_fn)
        t_b = now() - self.t0
        flops = 0.0
        prefill_tokens = 0
        for rid in rep.admitted:
            self.req[rid]["admitted"] = t_a
            n = len(self.req[rid]["prompt"])
            prefill_tokens += n
            flops += cost.prefill_flops(self.m, n)
        for rid, seen in list(self.active.items()):
            q = self.sched.requests[rid]
            if q.generated == seen:
                continue
            rec = self.req[rid]
            self.active[rid] = q.generated
            rec["times"].append(t_b)
            flops += cost.decode_flops(self.m, len(rec["prompt"])
                                       + q.generated)
            if rec["first_token"] is None:
                rec["first_token"] = t_b
            if q.generated >= q.max_new_tokens:
                rec["done"] = t_b
                del self.active[rid]
                if rec["key"] is not None:
                    self.source.finished(rec["key"], t_b)
        self.rounds.append({"t_a": t_a, "t_b": t_b, "flops": flops,
                            "prefill_tokens": prefill_tokens,
                            "preempted": len(rep.preempted),
                            "resumed": len(rep.resumed)})

    def run_until(self, t_stop: float, on_step=None) -> None:
        """Submit what is due and step until ``t_stop`` (seconds after
        the traffic origin); ``on_step(t)`` runs before each step."""
        while True:
            t = now() - self.t0
            if t >= t_stop:
                return
            for r in self.source.due(t):
                self.submit(r, t)
            if self.sched.idle:
                nd = self.source.next_due()
                wait = (t_stop if nd is None else min(nd, t_stop)) - t
                time.sleep(max(min(wait, 0.05), 0.0005))
                continue
            if on_step is not None:
                on_step(t)
            self.step()

    def drain(self, max_rounds: int = 10_000) -> None:
        """Step until every submitted request has finished."""
        for _ in range(max_rounds):
            if self.sched.idle:
                return
            self.step()
        raise RuntimeError("warm-up requests did not finish")


def annotate(obj, attr: str, label: str) -> None:
    """Wrap ``obj.attr`` in a profiler TraceAnnotation (instance level)."""
    import jax
    fn = getattr(obj, attr)

    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(label):
            return fn(*a, **k)

    setattr(obj, attr, wrapped)


def annotate_stages(sched):
    """Host spans around the scheduler's stages, the lane merge, the
    decode round and the block-table upload; returns the greedy pick
    (host-side argmax) wrapped in a span, to pass as ``sample_fn``."""
    import jax
    annotate(sched, "_retire_finished", "sched.retire")
    annotate(sched, "_admit_and_resume", "sched.admit")
    annotate(sched, "_preempt_for_waiters", "sched.preempt")
    annotate(sched.eng.stream, "adopt", "sched.adopt")
    annotate(sched.eng, "decode_round", "engine.decode_round")
    annotate(sched.eng.cache, "device_tables", "engine.table_upload")

    def argmax(lg):
        with jax.profiler.TraceAnnotation("engine.argmax"):
            return int(np.argmax(lg))

    return argmax


def drain_row_counts() -> Dict[str, float]:
    """Cumulative drained rows per opcode mnemonic (the engine's
    ``drain.rows`` counters, every stream)."""
    from repro.obs import metrics as obs_metrics
    out: Dict[str, float] = {}
    for (name, labels), v in obs_metrics.registry().counters.items():
        if name == "drain.rows":
            op = dict(labels)["opcode"]
            out[op] = out.get(op, 0.0) + v
    return out


# --------------------------------------------------------------------------
# correctness
# --------------------------------------------------------------------------
def pick_sample(drv: TrafficLoop, seed: int, want_tokens: int,
                t_end: float, want_requests: int = 4) -> List[int]:
    """Finished requests to compare, drawn from ``seed``: the one with
    the most served tokens, one that was preempted and resumed (when one
    finished), then seeded draws until the sample holds ``want_tokens``
    served tokens and ``want_requests`` requests.  Every chat request
    after the first of its tenant shares its system prompt's pages."""
    done = [rid for rid, r in drv.req.items()
            if r["done"] is not None and r["done"] <= t_end]
    if not done:
        return []
    rng = np.random.default_rng([int(seed), SAMPLE_STREAM])
    served = {rid: len(drv.req[rid]["served"]) for rid in done}
    pick = [max(done, key=lambda r: (served[r], -r))]
    pre = [rid for rid in done
           if drv.req[rid]["preemptions"] > 0 and rid not in pick]
    if pre:
        pick.append(pre[int(rng.integers(len(pre)))])
    rest = [rid for rid in done if rid not in pick]
    rng.shuffle(rest)
    for rid in rest:
        if sum(served[r] for r in pick) >= want_tokens \
                and len(pick) >= want_requests:
            break
        pick.append(rid)
    return pick


def _max(x: np.ndarray) -> float:
    return float(x.max()) if x.size else float("inf")


def compare(m: dict, seed: int, drv: TrafficLoop, sample: List[int],
            control: bool = False) -> Dict:
    """Served tokens of ``sample`` against the plain reference: the widest
    gap by which a served token's logit lies below the reference's best
    (and, with ``control``, the widest gap of the int8 forward's picks).

    Where the reference routes (its ``margin`` is finite, as for an MoE
    configuration), a position is compared only where it routes clearly
    at every layer: its k-th router logit lies above the next by at least
    ``correct.route_margin`` times the root mean square of its router
    logits.  A nearer tie falls either way under bf16 rounding and moves
    the token by a whole expert's share; ``skipped_share`` says how many
    positions that left out, and ``by_margin`` reads the gaps at a range
    of margins."""
    if not sample:
        return {"max_logit_gap": float("inf"), "mean_logit_gap": float("inf"),
                "control_max_logit_gap": float("inf"), "skipped_share": 1.0,
                "tokens": 0, "requests": 0, "preempted": 0,
                "complete": False}
    recs = [drv.req[r] for r in sample]
    gaps = spec.reference(m).logit_gaps(
        m, seed, [r["prompt"] for r in recs], [r["served"] for r in recs],
        control=control)
    every = np.concatenate([g["gap"] for g in gaps])
    margin = np.concatenate([g["margin"] for g in gaps])
    keep = margin >= float(m["correct"].get("route_margin", 0.0))
    ctrl = np.concatenate([g["gap_control"] for g in gaps]) if control \
        else None
    out = {"max_logit_gap": _max(every[keep]),
           "mean_logit_gap": float(every[keep].mean()) if keep.any()
           else float("inf"),
           "skipped_share": float(1.0 - keep.mean()),
           "gap_quantiles": [float(np.quantile(every, q))
                             for q in (0.5, 0.9, 0.99, 0.999)]}
    if control:
        out["control_max_logit_gap"] = _max(ctrl[keep])
        out["control_quantiles"] = [float(np.quantile(ctrl, q))
                                    for q in (0.5, 0.9, 0.99, 0.999)]
    if np.isfinite(margin).any():
        # (margin, program's widest gap, control's, share left out)
        out["by_margin"] = [
            [mg, _max(every[margin >= mg]),
             _max(ctrl[margin >= mg]) if control else None,
             float(np.mean(margin < mg))] for mg in MARGINS]
    return {**out,
            "tokens": sum(len(r["served"]) for r in recs),
            "requests": len(recs),
            "preempted": sum(1 for r in recs if r["preemptions"]),
            "complete": all(len(r["served"]) == r["max_new"] for r in recs)}


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------
def device_info(devs, chips: int) -> Dict:
    used = devs[:chips]
    peak = 0
    for d in used:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used), "memory_peak_bytes": peak}


def check_chips(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Failed(f"no TPU found (JAX platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise Failed(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


class TraceSlice:
    """Traces the last :data:`TRACE_SECONDS` of the window: started
    between rounds once the window's end is that near, inside a
    ``bench.window`` host span, and stopped after the window (stopping
    takes seconds, which must not stall the rounds being measured).
    Keeps the drained rows per opcode and the rounds inside it."""

    def __init__(self, drv: TrafficLoop, out: pathlib.Path, t_from: float):
        self.drv, self.out, self.t_from = drv, out, t_from
        self.on = self.done = False

    def __call__(self, t: float) -> None:
        import jax
        if self.on or self.done or t < self.t_from:
            return
        shutil.rmtree(self.out, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.out), profiler_options=opts)
        self.ann = jax.profiler.TraceAnnotation("bench.window")
        self.ann.__enter__()
        self.on = True
        self.rows0 = drain_row_counts()
        self.first_round = len(self.drv.rounds)

    def stop(self) -> None:
        import jax
        if not self.on:
            return
        self.ann.__exit__(None, None, None)
        self.rounds = self.drv.rounds[self.first_round:]
        rows1 = drain_row_counts()
        self.rows = {k: v - self.rows0.get(k, 0.0) for k, v in rows1.items()
                     if v > self.rows0.get(k, 0.0)}
        jax.profiler.stop_trace()
        self.on, self.done = False, True


@dataclasses.dataclass
class Served:
    """A cell's engine built, every shape warmed and the warm-up traffic
    run: the state the window drives."""
    eng: object
    sched: object
    drv: TrafficLoop
    t_win: float         #: window start on the traffic clock
    setup_s: float


def set_up(cell: Dict, seed: int, log=print, fault=None, trace=False,
           load: Optional[Dict] = None, t_from: float = T_START) -> Served:
    """Build the cell's ``ServingEngine`` over weights from ``seed``, wrap
    it in a ``RequestScheduler``, warm every shape the window uses (every
    drain bucket, one request per prompt length) and run ``warmup_s`` of
    the cell's traffic.  ``load`` replaces the cell's own (a sweep's
    rate); ``fault(eng, loop)``, called before the warm-up, lets a test
    break the timed path; set-up is timed from ``t_from``."""
    from repro.launch.scheduler import RequestScheduler, TenantSpec
    m, mix = cell["config"], cell["traffic"]
    count_compiles()
    eng = build_engine(m, seed)
    source = cell["generator"].make(mix, seed, m,
                                    cell["load"] if load is None else load)
    sched = RequestScheduler(eng, [TenantSpec(n, p)
                                   for n, p in source.tenants])
    drv = TrafficLoop(sched, source, m, eng.cache.page)
    if fault is not None:
        fault(eng, drv)
    warm_drain(eng)
    for r in source.warm_requests():
        drv.submit(r, 0.0)
    drv.drain()
    drv.req.clear()
    drv.rounds.clear()
    if trace:
        drv.sample_fn = annotate_stages(sched)
    drv.t0 = now()
    drv.run_until(float(mix["warmup_s"]))
    st = Served(eng, sched, drv, now() - drv.t0, now() - t_from)
    log(f"[bench] set-up {st.setup_s:.3f} s, {COMPILES['n']} backend "
        f"compiles ({COMPILES['s']:.2f} s)")
    return st


def drive(st: Served, seconds: float, on_step=None) -> float:
    """The window: ``seconds`` of the cell's traffic through
    ``RequestScheduler.step``; returns its end on the traffic clock."""
    COMPILES["in_window"] = True
    st.drv.run_until(st.t_win + seconds, on_step=on_step)
    COMPILES["in_window"] = False
    return now() - st.drv.t0


def release(st: Served) -> int:
    """Record what every request was served, then drop every reference
    to the program's state, so the reference (or the next engine) has the
    chip's memory.  Returns the dedup pages shared in the run."""
    for rid, rec in st.drv.req.items():
        q = st.sched.requests[rid]
        rec["served"] = list(q.tokens_out)
        rec["preemptions"] = q.preemptions
    dedup = st.eng.dedup_pages_shared
    st.eng.params = None
    st.eng = st.sched = st.drv.sched = st.drv.source = None
    gc.collect()
    return dedup


def run_cell(cell: Dict, seed: int, seconds: float, trace: bool,
             devs, log=print, fault=None, keep_trace: Optional[str] = None,
             control: bool = False) -> Tuple[Dict, Dict]:
    """One run of a resolved cell (``spec.resolve``); returns the result
    object and the comparison's details.  ``fault`` as in :func:`set_up`;
    ``keep_trace`` names a ``.json.gz`` file to keep the loaded trace in
    (for the reduction's test fixture); ``control`` puts the int8
    reference in the program's place: the numbers compared, and so
    ``correct``, are its first choices' gaps on the same prompts and
    tokens."""
    m, mix = cell["config"], cell["traffic"]
    w = cell["workload"]
    chips = w["chips"]
    peaks = None
    if devs[0].platform == "tpu":
        table = spec.load_json(spec.BENCH_DIR / "peaks.json")["kinds"]
        if devs[0].device_kind not in table:
            raise KeyError(f"no peaks for device kind "
                           f"{devs[0].device_kind!r} in bench/peaks.json")
        peaks = table[devs[0].device_kind]
    log(f"[bench] {w['name']}: device {devs[0].platform} "
        f"{devs[0].device_kind} x{len(devs)} (using {chips}), seed {seed}")
    st = set_up(cell, seed, log=log, fault=fault, trace=trace)
    drv, t_win = st.drv, st.t_win
    first = len(drv.rounds)
    tslice = TraceSlice(drv, OUT_DIR / "trace",
                        t_win + seconds - TRACE_SECONDS) if trace else None
    t_stop = drive(st, seconds, on_step=tslice)
    if tslice is not None:
        tslice.stop()
    dev = device_info(devs, chips)
    # -- end-to-end numbers ---------------------------------------------------
    win_rounds = drv.rounds[first:]
    due_in = [r for r in drv.req.values() if t_win <= r["due"] < t_stop]
    tokens = sum(1 for r in drv.req.values() for t in r["times"]
                 if t_win < t <= t_stop)
    window_s = t_stop - t_win
    ttft = stats.ttft_samples(due_in, t_stop)
    itl = stats.inter_token_gaps({k: r["times"] for k, r in drv.req.items()},
                                 t_win, t_stop)
    late = stats.lateness(due_in)
    admitted = [r for r in drv.req.values()
                if r["admitted"] is not None and r["admitted"] >= t_win]
    tpot = stats.time_per_output_token(
        {k: r["times"] for k, r in drv.req.items()}, t_win, t_stop)
    e2e = {
        "output_tokens_per_s": tokens / window_s,
        "ttft_p50_ms": (stats.percentile(ttft, 50) or 0.0) * 1e3,
        "itl_p95_ms": (stats.percentile(itl, 95) or 0.0) * 1e3,
        "itl_mean_ms": (stats.mean(itl) or 0.0) * 1e3,
        "setup_s": st.setup_s,
    }
    log(f"[bench] window {window_s:.3f} s: {len(win_rounds)} rounds, "
        f"{tokens} tokens, {len(due_in)} requests due "
        f"({sum(1 for r in due_in if r['done'] is not None)} finished), "
        f"{len(admitted)} admitted")
    log(f"[bench] ttft p50 {stats.percentile(ttft, 50)} s, p90 "
        f"{stats.percentile(ttft, 90)} s over {len(ttft)} requests "
        f"({sum(1 for r in due_in if r['first_token'] is None)} censored)")
    log(f"[bench] itl p50 {stats.percentile(itl, 50)} s, p90 "
        f"{stats.percentile(itl, 90)} s, p95 {stats.percentile(itl, 95)} s, "
        f"p99 {stats.percentile(itl, 99)} s, mean {e2e['itl_mean_ms']} ms "
        f"over {len(itl)} gaps")
    log(f"[bench] tpot p50 {stats.percentile(tpot, 50)} s, p90 "
        f"{stats.percentile(tpot, 90)} s over {len(tpot)} requests")
    log(f"[bench] generator lateness p50 {stats.percentile(late, 50)} s, "
        f"max {max(late) if late else None} s")
    log(f"[bench] preempted {sum(x['preempted'] for x in win_rounds)}, "
        f"resumed {sum(x['resumed'] for x in win_rounds)} in the window")
    log(f"[bench] backend compiles inside the window: {COMPILES['window']}")
    log(f"[bench] peak_bytes_in_use {dev['memory_peak_bytes']}")
    host = {
        "queue_wait_s": [r["admitted"] - r["due"] for r in admitted],
        "itl_s": itl,
        "flops": sum(x["flops"] for x in win_rounds),
        "rounds_s": sum(x["t_b"] - x["t_a"] for x in win_rounds),
        "traced_prefill_tokens": 0,
        "traced_drain_rows": {},
    }
    red = None
    if tslice is not None and tslice.done:
        from bench import trace_reduce
        host["traced_prefill_tokens"] = sum(x["prefill_tokens"]
                                            for x in tslice.rounds)
        host["traced_drain_rows"] = tslice.rows
        files = sorted((OUT_DIR / "trace").rglob("*.xplane.pb"))
        loaded = trace_reduce.load(str(files[-1]),
                                   devices=[d.id for d in devs[:chips]])
        if keep_trace:
            import gzip
            with gzip.open(keep_trace, "wt") as f:
                json.dump(loaded, f)
        red = trace_reduce.reduce(loaded)
        shutil.rmtree(OUT_DIR / "trace", ignore_errors=True)
        log(f"[bench] traced slice {red['window_s']:.3f} s over "
            f"{len(tslice.rounds)} rounds, device busy {red['busy_s']:.3f} s")
    # -- free the program, then compare with the reference -------------------
    dedup = release(st)
    sample = pick_sample(drv, seed, m["correct"]["sample_tokens"], t_stop,
                         m["correct"].get("sample_requests", 4))
    limits = m["correct"]["limits"]
    t_ref = now()
    cmp = compare(m, seed, drv, sample, control=control)
    log(f"[bench] compared {cmp['requests']} requests, {cmp['tokens']} "
        f"served tokens ({cmp['preempted']} of them preempted and "
        f"resumed; dedup shared {dedup} pages in the run; "
        f"{cmp['skipped_share']:.4f} of positions routed within the "
        f"margin, left out) in {now() - t_ref:.3f} s"
        + (" -- the int8 control in the program's place" if control else ""))
    if "by_margin" in cmp:
        log(f"[bench] routing margin, widest gap, control's, share left out: "
            f"{cmp['by_margin']}")
    read = {"max_logit_gap": cmp["control_max_logit_gap" if control
                                 else "max_logit_gap"]}
    compared = {k: {"value": read[k], "limit": v} for k, v in limits.items()}
    result: Dict = {
        "correct": bool(cmp["complete"] and all(
            c["value"] <= c["limit"] for c in compared.values())),
        "attempted": len(due_in), "failed": 0, "metrics": {}, "device": dev}
    if trace:
        run = {"trace": red, "host": host, "model": m,
               "page": drv.page, "peaks": peaks}
        for pm in cell["per_layer"] if red is not None else []:
            v = spec.metric_reader(pm["name"])(run)
            if v is not None:
                result["metrics"][pm["name"]] = {"value": v,
                                                 "unit": pm["unit"]}
        if red is not None:
            from bench import trace_reduce
            dev["busy_s"] = red["busy_s"]
            dev["window_s"] = red["window_s"]
            result["breakdown"] = trace_reduce.breakdown(red)
    else:
        for em in cell["end_to_end"]:
            result["metrics"][em["name"]] = {"value": e2e[em["name"]],
                                             "unit": em["unit"]}
    result["compared"] = compared
    return result, cmp


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="keep the loaded trace in this .json.gz file")
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    cell = spec.resolve(bench, args.workload)
    try:
        import jax
        devs = check_chips(cell["workload"]["chips"])
    except Failed as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    from repro.launch import serve
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    print(f"[bench] compile cache: {serve.setup_compile_cache()}")
    result, _ = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                         devs, log=lambda s: print(s, flush=True),
                         keep_trace=args.keep_trace)
    for k, v in result["compared"].items():
        print(f"compared {k} {v['value']} limit {v['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
