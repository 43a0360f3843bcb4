"""Dispatch-path benchmark: fused command-queue flush vs seed per-op fan-out.

Measures, for mixed copy+zero batches over a {"k","v"} pool pair:

* launches per flush (via the kernels/fused_dispatch.py launch hook),
* wall-clock per flushed batch (median of repeated flushes, post-warmup),
* bytes physically moved (identical across paths — the win is dispatch).

Since schema v3 it also A/Bs full SERVING ROUNDS (admission prefill
staging + CoW fork splits + decode) through the real ServingEngine:
``fused_staging`` (staging pools + cross-pool promotion through the
queue — ONE bulk-movement launch per round) vs the seed ``_stage_legacy``
scatter path (one ad-hoc dispatch per pool per admission).  Schema v4
adds the ``ring_staging`` path — staging pools sized as a
``max_admit_pages`` RING through the PoolGroup per-pool block counts —
and tracks ``pool_bytes_resident`` per serve_round row, so the ~2x
serving-memory reduction is recorded alongside launches/round and
wall-clock (greedy tokens are asserted bitwise-identical to the
full-twin path in ``summary.ring_tokens_match``).  Schema v5 adds the
``burst_admission`` serve_round leg: rounds admitting MORE staged pages
than the ring's nominal capacity, single-buffered (early-flush launch)
vs double-buffered (shadow half absorbs the burst at 1.0 launches/round,
the CommandStream/source-hazard redesign headline).  Schema v6 adds the
``fault_recovery`` leg: a reference serve run vs one with an injected
launch failure + donated-admission error, auto-recovered from the ticket
journal and the background checkpoint stream — greedy tokens must stay
bitwise-identical (in admission order) and the serve flush must return
to <= 1 launch/round within 2 rounds.

Schema v7 adds the ``serve_traffic`` section: closed-loop traffic through
the :class:`~repro.launch.scheduler.RequestScheduler` (continuous
batching, per-tenant QoS lanes on dedicated command streams, preemption
by demotion to the spill pools) under Poisson and bursty arrivals — the
gate holds launches/round at <= 1.0 WITH churn and preemption active,
and preempted-then-resumed sequences must produce bitwise-identical
greedy tokens vs an unpreempted run (CPU and the 8-device mesh leg).

Schema v8 adds two legs for the in-memory bitwise opcodes
(OP_AND/OP_OR/OP_NOT, the Ambit triple-row-activation analogue):
``bitwise`` A/Bs mixed memand/memor/memnot flushes through the fused
table vs the seed per-pool fan-out — the gate holds the fused path at
1.0 launch/flush AND asserts the two paths' final pool bytes are
bit-identical — and ``dedup_admit`` drives the duplicated-prompt
serving leg (fig34_multitenant.run_dedup): fingerprint-matched prompt
pages collapse into shared CoW blocks on admission, so peak resident KV
bytes drop while greedy tokens stay bitwise-equal to a dedup-off twin
at <= 1.0 launches/round.

Schema v9 adds the ``autotune`` section: a summary of the committed
per-backend TunedProfile (``configs/tuned/<backend>.json``, written by
``benchmarks/bench_autotune.py``) — the constants the profiler-driven
sweep picked and the measured ``us_per_flush`` win vs the hand-picked
defaults — and all wall-clock loops now time through the shared
``repro.obs`` stopwatch instead of raw ``time.perf_counter()``.

Emits ``BENCH_dispatch.json``:

{
  "schema": "bench_dispatch/v9",
  "backend": "cpu" | "tpu",
  "block": [page, KVH, D], "nblk": int, "pools": ["k", "v"],
  "rows": [{
      "batch": int,            # commands per flush (copies + zeros)
      "path": "fused"|"seed",  # queue+fused launch vs per-op fan-out
      "launches_per_flush": float,
      "table_len": int,        # padded table length (bucket vs max_requests)
      "us_per_flush": float,   # median wall-clock
      "bytes_moved": int       # bytes one flush moves (per-flush, not
                               # cumulative over the measurement loop)
  }],
  "summary": {"speedup_small_batch": float},  # seed/fused us at batch<=8
  "mesh": {                    # multi-device A/B (8 forced host devices,
                               # measured in a subprocess; null if it failed)
      "devices": 8, "mesh_shape": [2, 4],
      "rows": [... same row schema, paths "fused"|"seed" ...],
      "summary": {"speedup": float,          # seed/fused wall-clock
                  "launches_fused": float,   # per flush (the "1" this PR
                  "launches_seed": float}    # buys vs the fan-out)
  },
  "serve_round": {             # full serving rounds through ServingEngine
      "arch": str, "max_seqs": int, "rounds": int, "admit_rounds": int,
      "rows": [{
          "path": "fused_staging"|"ring_staging"|"seed_staging",
          "launches_admit_round": float, # bulk-movement launches in rounds
                                         # that admit (1.0 fused: prefill
                                         # staging rides the round's flush)
          "launches_per_round": float,   # mean over ALL measured rounds
          "us_per_round": float,         # median post-warmup wall-clock
          "stage_promotions": int,       # blocks promoted via the queue
          "pool_bytes_resident": int,    # engine pool bytes (KV + staging)
          "stage_capacity": int          # staging slots (ring vs twin)
      }],
      "summary": {"speedup": float, "launches_fused": float,
                  "launches_seed": float,
                  "staging_memory_reduction": float,  # twin/ring resident
                  "ring_tokens_match": bool},  # greedy tokens bitwise ==
      "burst_admission": {     # admissions/round x pages > ring capacity
          "ring_pages": int, "admits_per_round": int, "rounds": int,
          "rows": [{
              "path": "single_ring"|"double_ring",
              "launches_per_round": float,  # 1.0 double vs >1.0 single
              "us_per_round": float,
              "stage_capacity": int         # ring slots (2x when double)
          }],
          "summary": {"launches_single": float, "launches_double": float,
                      "tokens_match": bool}  # double == single, bitwise
      },
      "fault_recovery": {      # injected failures + in-place recovery
          "rounds": int, "fault_round": int, "readmit_round": int,
          "ckpt_pages": int,   # spill blocks per pool (background ckpt)
          "injections": ["launch_failure", "donation_error"],
          "serve_launches_ref": [int],    # per-round serve-flush launches
          "serve_launches_fault": [int],  # -1 = flush failed + recovered
          "summary": {"tokens_match": bool,      # vs the reference run
                      "rounds_to_recover": int,  # <= 2 gated by smoke
                      "evicted": int,            # admissions re-admitted
                      "max_launches_post_recovery": int,
                      "ckpt_active": bool}  # ckpt stream kept ticking
      },
      "mesh": {"devices": 8, "mesh_shape": [2, 4],    # sharded-batch leg
               "rows": [...], "summary": {...}} | null
  },
  "serve_traffic": {           # RequestScheduler under closed-loop load
      "rounds": int, "tenants": {"gold": 2, "silver": 1, "free": 0},
      "legs": {"poisson"|"bursty": {
          "max_launches_per_round": float,  # gate: <= 1.0 under churn
          "mean_launches_per_round": float,
          "submitted": int, "completed": int,
          "preempted_requests": int,        # demoted at least once
          "per_tenant": {tenant: {"submitted", "completed",
              "goodput_tok_s", "p50_token_latency_rounds",
              "p99_token_latency_rounds", "p50_ttft_rounds",
              "preemptions"}}}},
      "preempt_parity": {      # demote -> resume vs unpreempted run
          "tokens_match": bool,             # bitwise greedy parity
          "preempted": int,                 # victims actually demoted
          "max_launches_per_round": float},
      "mesh": {"devices": 8, "mesh_shape": [2, 4],
               "preempt_parity": {...}} | null
  },
  "bitwise": {                 # OP_AND/OP_OR/OP_NOT dispatch A/B
      "rows": [{
          "batch": int,            # bitwise rows per flush (AND+OR+NOT mix)
          "path": "fused"|"seed",
          "launches_per_flush": float,  # 1.0 fused vs per-opcode-chunk
          "us_per_flush": float,
          "bytes_bitwise": int     # dst bytes one flush computes
      }],
      "summary": {"speedup": float, "launches_fused": float,
                  "launches_seed": float,
                  "bitwise_match": bool}  # final pool bytes identical
  },
  "dedup_admit": {             # duplicated-prompt admission dedup leg
      "tenants": int, "rounds": int,
      "kv_bytes_live_on": int,   # peak resident KV bytes, dedup on
      "kv_bytes_live_off": int,  # ... and the dedup-off twin
      "resident_reduction": float,  # 1 - on/off (> 0 gated by smoke)
      "dedup_hits": int, "pages_shared": int, "bytes_saved": int,
      "tokens_match": bool,      # greedy tokens bitwise == dedup-off
      "max_launches_per_round": float   # gate: <= 1.0
  },
  "autotune": {                # committed TunedProfile summary (v9)
      "profile": {...} | null, # TunedProfile.to_dict() minus sweep rows
      "path": str,             # configs/tuned/<backend>.json
      "tuned_vs_default_us_ratio": float  # < 1.0 = tuned wins
  }
}

CLI: PYTHONPATH=src python benchmarks/bench_dispatch.py [--out PATH]
                         [--skip-mesh] [--skip-serve] [--serve-smoke]
                         [--traffic-smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import RowCloneEngine, SubarrayAllocator
from repro.kernels import fused_dispatch as fd
from repro.obs import metrics as obs_metrics

BLOCK = (16, 2, 64)          # page x KVH x head_dim
NBLK = 1024
NSLABS = 4
BATCHES = (2, 4, 8, 32, 128)
REPS = 30
MESH_SHAPE = (2, 4)          # 8 forced host devices in the subprocess
MESH_BATCHES = (8, 32)
MESH_REPS = 10


def _mk_engine(use_fused: bool, mesh=None) -> RowCloneEngine:
    alloc = SubarrayAllocator(NBLK, NSLABS, reserved_zero_per_slab=1)
    key = jax.random.key(0)
    pools = {
        "k": jax.random.normal(key, (NBLK,) + BLOCK, jnp.float32),
        "v": jax.random.normal(jax.random.key(1), (NBLK,) + BLOCK,
                               jnp.float32),
    }
    # max_requests=256 is the seed default the fan-out path pads to
    return RowCloneEngine(pools, alloc, mesh=mesh, max_requests=256,
                          use_fused=use_fused)


def _flush_once(eng: RowCloneEngine, batch: int, round_i: int) -> None:
    """One mixed flush: ~3/4 copies (FPM+PSM mix), ~1/4 zero-inits.
    Source/dest ids rotate per round so jit caches stay warm but data
    differs."""
    n_zero = max(batch // 4, 1)
    n_copy = batch - n_zero
    base = (round_i * batch) % (NBLK // 4)
    srcs = [1 + (base + i) % (NBLK // 4) for i in range(n_copy)]
    dsts = [NBLK // 2 + (base + i) % (NBLK // 4) for i in range(n_copy)]
    zeros = [3 * NBLK // 4 + (base + i) % (NBLK // 8) for i in range(n_zero)]
    eng.alloc.mark_written(srcs)
    with eng.batch():
        eng.memcopy(list(zip(srcs, dsts)))
        eng.materialize_zeros(zeros)


def _bench_path(use_fused: bool, batch: int, mesh=None,
                reps: int = REPS) -> Dict:
    eng = _mk_engine(use_fused, mesh=mesh)
    events: List = []
    hook = lambda n, p, mech: events.append((n, p, mech))
    fd.add_launch_hook(hook)
    try:
        # warmup (compile) flushes
        for r in range(3):
            _flush_once(eng, batch, r)
        events.clear()
        eng.stats = type(eng.stats)()   # per-flush byte accounting below
        times = []
        for r in range(reps):
            with obs_metrics.Stopwatch() as sw:
                _flush_once(eng, batch, 100 + r)
                jax.block_until_ready(list(eng.pools.values()))
            times.append(sw.s)
    finally:
        fd.remove_launch_hook(hook)
    bytes_moved = eng.stats.bytes_fpm + eng.stats.bytes_psm + \
        eng.stats.bytes_baseline
    bytes_moved += eng.stats.zero_materialized * eng._block_bytes()
    bytes_moved //= reps
    return {
        "batch": batch,
        "path": "fused" if use_fused else "seed",
        "launches_per_flush": len(events) / reps,
        "table_len": max((e[0] for e in events), default=0),
        "us_per_flush": float(np.median(times) * 1e6),
        "bytes_moved": int(bytes_moved),
    }


# ---------------------------------------------------------------------------
# bitwise A/B — in-memory OP_AND/OP_OR/OP_NOT rows through the same flush
# ---------------------------------------------------------------------------

BITWISE_BATCHES = (8, 32)


def _flush_bitwise(eng: RowCloneEngine, batch: int, round_i: int) -> None:
    """One mixed bitwise flush: ~1/3 each AND/OR/NOT over disjoint id
    ranges (no RAW/WAW, so the fused path drains as exactly one launch).
    Ids rotate per round so jit caches stay warm but data differs."""
    third = max(batch // 3, 1)
    span = NBLK // 8
    base = (round_i * batch) % span
    a = [1 + (base + i) % span for i in range(third)]
    b = [NBLK // 4 + (base + i) % span for i in range(third)]
    d = [NBLK // 2 + (base + i) % span for i in range(third)]
    eng.alloc.mark_written(a + b)
    with eng.batch():
        eng.memand(list(zip(a, b, d)))
        eng.memor(list(zip(b, a, [x + span for x in d])))
        eng.memnot(list(zip(a, [x + 2 * span for x in d])))


def _bench_bitwise_path(use_fused: bool, batch: int, reps: int = REPS):
    """Measure one bitwise path; returns (engine, row) so the caller can
    compare final pool bytes across paths."""
    eng = _mk_engine(use_fused)
    events: List = []
    hook = lambda n, p, mech: events.append((n, p, mech))
    fd.add_launch_hook(hook)
    try:
        for r in range(3):
            _flush_bitwise(eng, batch, r)
        events.clear()
        eng.stats = type(eng.stats)()
        times = []
        for r in range(reps):
            with obs_metrics.Stopwatch() as sw:
                _flush_bitwise(eng, batch, 100 + r)
                jax.block_until_ready(list(eng.pools.values()))
            times.append(sw.s)
    finally:
        fd.remove_launch_hook(hook)
    return eng, {
        "batch": batch,
        "path": "fused" if use_fused else "seed",
        "launches_per_flush": len(events) / reps,
        "us_per_flush": float(np.median(times) * 1e6),
        "bytes_bitwise": int(eng.stats.bytes_bitwise // reps),
    }


def _run_bitwise_section() -> Dict:
    """A/B the bitwise opcodes fused vs seed and assert both paths left
    bit-identical pool contents (compared through uint views — float
    equality would miss NaN-pattern divergence)."""
    rows = []
    match = True
    for batch in BITWISE_BATCHES:
        engs = {}
        for use_fused in (True, False):
            eng, row = _bench_bitwise_path(use_fused, batch)
            engs[row["path"]] = eng
            rows.append(row)
        for name in engs["fused"].pools:
            fa = np.asarray(engs["fused"].pools[name]).view(np.uint32)
            sa = np.asarray(engs["seed"].pools[name]).view(np.uint32)
            if not np.array_equal(fa, sa):
                match = False
    f = [r for r in rows if r["path"] == "fused"]
    s = [r for r in rows if r["path"] == "seed"]
    return {
        "rows": rows,
        "summary": {
            "speedup": float(np.mean([r["us_per_flush"] for r in s]) /
                             np.mean([r["us_per_flush"] for r in f])),
            "launches_fused": float(np.mean(
                [r["launches_per_flush"] for r in f])),
            "launches_seed": float(np.mean(
                [r["launches_per_flush"] for r in s])),
            "bitwise_match": match,
        },
    }


def _print_bitwise(section: Dict) -> None:
    for r in section["rows"]:
        print(f"  bitwise {r['batch']:>4} {r['path']:>6} "
              f"{r['launches_per_flush']:>6.2f} launches/flush "
              f"{r['us_per_flush']:>10.1f} us/flush "
              f"{r['bytes_bitwise'] / 1e6:>6.1f} MB computed")
    s = section["summary"]
    print(f"  bitwise flush speedup {s['speedup']:.2f}x  (launches "
          f"{s['launches_fused']:.2f} fused vs {s['launches_seed']:.2f} "
          f"seed, pools bit-identical: {s['bitwise_match']})")


# ---------------------------------------------------------------------------
# serve_round A/B — full serving rounds through the real ServingEngine
# ---------------------------------------------------------------------------

SERVE_ARCH = "llama3.2-3b"
SERVE_ROUNDS = 8
SERVE_ADMIT_ROUNDS = 4
SERVE_WARMUP = 2             # rounds excluded from the median (compiles)
SERVE_MAX_BLOCKS = 16        # KV nblk = 8 * 16 = 128 blocks
SERVE_RING_PAGES = 8         # staging-ring slots (vs the 128-slot twin)

#: (row label, fused_staging, max_admit_pages) serve_round legs — 0 is
#: ServingEngine.FULL_TWIN (max_admit_pages defaults to the policy-derived
#: ring since v5, so the twin baseline opts out explicitly)
SERVE_PATHS = (("fused_staging", True, 0),
               ("ring_staging", True, SERVE_RING_PAGES),
               ("seed_staging", False, 0))

#: burst_admission leg: rounds park BURST_ADMITS x 1 page into a
#: BURST_RING_PAGES-slot ring — past nominal capacity, so the
#: single-buffered ring early-flushes while the double-buffered shadow
#: half keeps the round at one launch
BURST_RING_PAGES = 2
BURST_ADMITS = 3
BURST_ROUNDS = 4

#: fault_recovery leg: a reference serve run vs one with an injected
#: launch failure (FAULT_ROUND) and a donated-admission error
#: (FAULT_READMIT_ROUND), auto-recovered in place with a background
#: checkpoint stream of FAULT_CKPT_PAGES spill blocks per pool
FAULT_ROUNDS = 6
FAULT_ROUND = 1
FAULT_READMIT_ROUND = 3
FAULT_CKPT_PAGES = 8


def _bench_serve_path(path: str, fused_staging: bool,
                      max_admit_pages: Optional[int], mesh=None) -> Dict:
    """One serving-round A/B leg: admit a request per round for the first
    ``SERVE_ADMIT_ROUNDS`` rounds, fork once, decode every round.  Reports
    bulk-movement launches/round (hook), median wall-clock/round, and the
    engine's resident pool bytes (the staging-ring headline).  The row
    carries the greedy token streams under a private ``_tokens`` key so
    ``_serve_summary`` can assert ring-vs-twin bitwise parity (stripped
    before the row is written)."""
    from repro.configs import get_config
    from repro.launch.serve import ServingEngine
    from repro.models import build_model, split_params
    cfg = get_config(SERVE_ARCH).reduced()
    model = build_model(cfg)
    params, _ = split_params(model.init_params(jax.random.key(0)))
    eng = ServingEngine(cfg, params, mesh=mesh, max_seqs=8,
                        max_blocks_per_seq=SERVE_MAX_BLOCKS,
                        fused_staging=fused_staging,
                        max_admit_pages=max_admit_pages)
    rng = np.random.default_rng(0)
    events: List = []
    hook = lambda n, p, mech: events.append(mech)
    fd.add_launch_hook(hook)
    launches, times, admitted = [], [], []
    sids: List[int] = []
    try:
        for r in range(SERVE_ROUNDS):
            n0 = len(events)
            with obs_metrics.Stopwatch() as sw:
                if r < SERVE_ADMIT_ROUNDS:
                    sids.append(eng.add_request(rng.integers(
                        2, cfg.vocab_size, size=24).astype(np.int32)))
                if r == SERVE_ADMIT_ROUNDS:
                    eng.fork(sids[0], 1)     # CoW splits on later appends
                eng.decode_round()
                jax.block_until_ready([eng.engine.pools["k"],
                                       eng.engine.pools["v"]])
            times.append(sw.s)
            launches.append(len(events) - n0)
            admitted.append(r < SERVE_ADMIT_ROUNDS)
    finally:
        fd.remove_launch_hook(hook)
    meas = slice(SERVE_WARMUP, None)
    admit_launches = [l for l, a in zip(launches[meas], admitted[meas]) if a]
    return {
        "path": path,
        # admission rounds exercise the staging path: prefill + promotion
        # + decode.  1.0 fused (ONE launch covers it) vs 2+ for the seed's
        # per-pool ad-hoc scatters.
        "launches_admit_round": float(np.mean(admit_launches)),
        "launches_per_round": float(np.mean(launches[meas])),
        "us_per_round": float(np.median(times[meas]) * 1e6),
        "stage_promotions": int(eng.engine.stats.stage_promotions),
        "pool_bytes_resident": int(eng.engine.pool_bytes_resident()),
        "stage_capacity": int(eng.engine.stage_capacity),
        "_tokens": {str(s): t for s, t in eng.tokens.items()},
    }


def _bench_burst_path(path: str, double_buffer: bool) -> Dict:
    """One burst-admission leg (CPU): every round admits ``BURST_ADMITS``
    one-page prompts into a ``BURST_RING_PAGES``-slot staging ring, then
    decodes.  The single-buffered ring must early-flush mid-round (extra
    launch); the double-buffered ring's shadow half keeps the round at
    one launch.  Rows carry ``_tokens`` for the cross-path parity check
    (stripped by ``_burst_summary``).  (The mesh burst leg lives in the
    test suite — tests/test_serving_staging.py MESH_SERVE_CHILD.)"""
    from repro.configs import get_config
    from repro.launch.serve import ServingEngine
    from repro.models import build_model, split_params
    cfg = get_config(SERVE_ARCH).reduced()
    model = build_model(cfg)
    params, _ = split_params(model.init_params(jax.random.key(0)))
    eng = ServingEngine(cfg, params,
                        max_seqs=BURST_ADMITS * BURST_ROUNDS,
                        max_blocks_per_seq=SERVE_MAX_BLOCKS,
                        max_admit_pages=BURST_RING_PAGES,
                        double_buffer=double_buffer)
    rng = np.random.default_rng(0)
    events: List = []
    hook = lambda n, p, mech: events.append(mech)
    fd.add_launch_hook(hook)
    launches, times = [], []
    try:
        for r in range(BURST_ROUNDS):
            n0 = len(events)
            with obs_metrics.Stopwatch() as sw:
                for _ in range(BURST_ADMITS):
                    eng.add_request(rng.integers(
                        2, cfg.vocab_size, size=24).astype(np.int32))
                eng.decode_round()
                jax.block_until_ready([eng.engine.pools["k"],
                                       eng.engine.pools["v"]])
            times.append(sw.s)
            launches.append(len(events) - n0)
    finally:
        fd.remove_launch_hook(hook)
    meas = slice(SERVE_WARMUP, None)
    return {
        "path": path,
        "launches_per_round": float(np.mean(launches[meas])),
        "us_per_round": float(np.median(times[meas]) * 1e6),
        "stage_capacity": int(eng.engine.stage_capacity),
        "_tokens": {str(s): t for s, t in eng.tokens.items()},
    }


def _burst_summary(rows: List[Dict]) -> Dict:
    """Cross-path burst summary; strips ``_tokens`` in place."""
    s = next(r for r in rows if r["path"] == "single_ring")
    d = next(r for r in rows if r["path"] == "double_ring")
    tokens = {r["path"]: r.pop("_tokens") for r in rows}
    return {
        "launches_single": s["launches_per_round"],
        "launches_double": d["launches_per_round"],
        "tokens_match": tokens["single_ring"] == tokens["double_ring"],
    }


def _run_burst_section() -> Dict:
    rows = [_bench_burst_path("single_ring", False),
            _bench_burst_path("double_ring", True)]
    return {
        "ring_pages": BURST_RING_PAGES,
        "admits_per_round": BURST_ADMITS,
        "rounds": BURST_ROUNDS,
        "rows": rows,
        "summary": _burst_summary(rows),
    }


def _drive_fault_rounds(eng, prompts, plan=None):
    """Drive FAULT_ROUNDS serving rounds, injecting the plan's failures
    at FAULT_ROUND (launch failure on the round's next drain) and
    FAULT_READMIT_ROUND (donation error on the third admission, then
    re-admission of the evicted prompt).  Returns (tokens in admission
    order, per-round serve-flush launches with -1 marking a round whose
    flush failed and recovered)."""
    from repro.runtime.fault import InjectedFault
    order, serve_launches = [], []
    for p in prompts[:2]:
        order.append(eng.add_request(p))
    for r in range(FAULT_ROUNDS):
        if plan is not None and r == FAULT_ROUND:
            plan.launch_failures += (eng.engine.next_flush_index,)
        if r == FAULT_READMIT_ROUND:
            if plan is not None:
                plan.donation_errors += (eng._admission_ordinal,)
                try:
                    eng.add_request(prompts[2])
                except InjectedFault:
                    pass        # evicted; re-admitted below
            order.append(eng.add_request(prompts[2]))
        eng.decode_round()
        t = eng.last_ticket     # None = the round's flush failed and
        # recover() ran (recovery resets the ticket); its launches are
        # the round's serve-stream accounting otherwise
        serve_launches.append(int(t.launches) if t is not None else -1)
    return ([eng.tokens[s] for s in order if s in eng.tokens],
            serve_launches)


def _run_fault_section() -> Dict:
    """fault_recovery serve leg (CPU): greedy tokens under injected
    failures + auto-recovery must match the failure-free run bitwise (in
    admission order — the evicted admission re-admits under a new sid),
    and the serve flush must return to <= 1 launch/round within
    ``rounds_to_recover`` rounds of each fault.  Both engines run the
    background checkpoint stream so the rows stay comparable."""
    import tempfile

    from repro.configs import get_config
    from repro.launch.serve import ServingEngine
    from repro.models import build_model, split_params
    from repro.runtime.fault import FaultPlan
    cfg = get_config(SERVE_ARCH).reduced()
    model = build_model(cfg)
    params, _ = split_params(model.init_params(jax.random.key(0)))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, cfg.vocab_size, size=24).astype(np.int32)
               for _ in range(3)]

    def mk(plan):
        return ServingEngine(
            cfg, params, max_seqs=8, max_blocks_per_seq=SERVE_MAX_BLOCKS,
            fault_plan=plan, auto_recover=plan is not None,
            ckpt_pages=FAULT_CKPT_PAGES,
            ckpt_dir=tempfile.mkdtemp(prefix="bench_fault_ckpt_"))

    ref_tokens, ref_launches = _drive_fault_rounds(mk(None), prompts)
    plan = FaultPlan()
    eng = mk(plan)
    tokens, launches = _drive_fault_rounds(eng, prompts, plan)
    # rounds after the fault round until the serve flush succeeds again
    # at <= 1 launch (0 = the fault round itself still flushed cleanly)
    rounds_to_recover = next(
        (i for i, l in enumerate(launches[FAULT_ROUND:])
         if 0 <= l <= 1), len(launches))
    return {
        "rounds": FAULT_ROUNDS,
        "fault_round": FAULT_ROUND,
        "readmit_round": FAULT_READMIT_ROUND,
        "ckpt_pages": FAULT_CKPT_PAGES,
        "injections": [k for k, _ in plan.fired],
        "serve_launches_ref": ref_launches,
        "serve_launches_fault": launches,
        "summary": {
            "tokens_match": tokens == ref_tokens,
            "rounds_to_recover": int(rounds_to_recover),
            "evicted": len(eng.evicted_sids),
            "max_launches_post_recovery": int(
                max(launches[FAULT_ROUND + 1:])),
            "ckpt_active": bool(eng.pool_ckpt._cursor > 0
                                or eng.pool_ckpt.passes > 0),
        },
    }


def _serve_summary(rows: List[Dict]) -> Dict:
    """Cross-path summary; strips the private ``_tokens`` keys in place."""
    f = next(r for r in rows if r["path"] == "fused_staging")
    g = next(r for r in rows if r["path"] == "ring_staging")
    s = next(r for r in rows if r["path"] == "seed_staging")
    tokens = {r["path"]: r.pop("_tokens") for r in rows}
    return {
        "speedup": float(s["us_per_round"] / f["us_per_round"]),
        "launches_fused": f["launches_admit_round"],
        "launches_seed": s["launches_admit_round"],
        # the v4 headline: ring staging vs full twin, same tokens
        "staging_memory_reduction": float(f["pool_bytes_resident"]
                                          / g["pool_bytes_resident"]),
        "ring_tokens_match": tokens["ring_staging"]
        == tokens["fused_staging"],
    }


def _serve_child() -> None:
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()).reshape(MESH_SHAPE),
                ("data", "model"))
    rows = [_bench_serve_path(*p, mesh=mesh) for p in SERVE_PATHS]
    summary = _serve_summary(rows)          # strips _tokens (unserializable
    print("SERVEROWS:" + json.dumps({"rows": rows,      # sets aside)
                                     "summary": summary}))


def _run_serve_section(skip_mesh: bool) -> Dict:
    rows = [_bench_serve_path(*p) for p in SERVE_PATHS]
    section = {
        "arch": f"{SERVE_ARCH} (reduced)",
        "max_seqs": 8,
        "rounds": SERVE_ROUNDS,
        "admit_rounds": SERVE_ADMIT_ROUNDS,
        "rows": rows,
        "summary": _serve_summary(rows),
        "burst_admission": _run_burst_section(),
        "fault_recovery": _run_fault_section(),
        "mesh": None,
    }
    if skip_mesh:
        return section
    payload = _child_payload("--serve-mesh-child", "SERVEROWS:")
    section["mesh"] = {
        "devices": int(np.prod(MESH_SHAPE)),
        "mesh_shape": list(MESH_SHAPE),
        "rows": payload["rows"],
        "summary": payload["summary"],
    }
    return section


# ---------------------------------------------------------------------------
# serve_traffic — RequestScheduler under closed-loop Poisson/bursty load
# ---------------------------------------------------------------------------

TRAFFIC_ROUNDS = 32
TRAFFIC_PATTERNS = ("poisson", "bursty")
TRAFFIC_PARITY_TOKENS = 8


def _traffic_driver():
    """Import the traffic driver from the sibling benchmark module."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import fig34_multitenant
    finally:
        sys.path.pop(0)
    return fig34_multitenant


DEDUP_ROUNDS = 4
DEDUP_TENANTS = 4


def _run_dedup_section() -> Dict:
    """Duplicated-prompt admission leg — fig34_multitenant.run_dedup
    (dedup-on vs dedup-off twin at the same seed)."""
    mt = _traffic_driver()
    return mt.run_dedup(rounds=DEDUP_ROUNDS, seed=0, arch=SERVE_ARCH,
                        tenants=DEDUP_TENANTS)


def _print_dedup(row: Dict) -> None:
    print(f"  dedup_admit ({row['tenants']} tenants, {row['rounds']} "
          f"rounds): resident KV {row['kv_bytes_live_on'] / 1e6:.1f} vs "
          f"{row['kv_bytes_live_off'] / 1e6:.1f} MB "
          f"({row['resident_reduction']:.0%} saved), "
          f"{row['pages_shared']} pages shared / {row['dedup_hits']} "
          f"admission hits, tokens match: {row['tokens_match']}, max "
          f"{row['max_launches_per_round']:.1f} launches/round")


def _traffic_parity(mesh=None) -> Dict:
    """Preempt→demote→resume greedy-token parity vs an unpreempted run.

    A deliberately tiny engine (2 batch slots) runs two free-tenant
    requests; a gold request arrives mid-flight and must preempt one.
    Every request's token stream must match, bitwise, the same prompts
    decoded on a roomy engine that never preempts — the demoted bytes
    parked in the spill slots ARE the KV pages.  Also reports the worst
    round's launch count (preemption must not cost extra launches)."""
    from repro.configs import get_config
    from repro.launch.scheduler import RequestScheduler, TenantSpec
    from repro.launch.serve import ServingEngine
    from repro.models import build_model, split_params
    cfg = get_config(SERVE_ARCH).reduced()
    model = build_model(cfg)
    params, _ = split_params(model.init_params(jax.random.key(0)))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, cfg.vocab_size, size=16).astype(np.int32)
               for _ in range(3)]
    tenants = [TenantSpec("gold", 2), TenantSpec("free", 0)]

    def drive(eng):
        sched = RequestScheduler(eng, tenants)
        rids = [sched.submit("free", prompts[0],
                             max_new_tokens=TRAFFIC_PARITY_TOKENS),
                sched.submit("free", prompts[1],
                             max_new_tokens=TRAFFIC_PARITY_TOKENS)]
        sched.step()
        sched.step()
        rids.append(sched.submit("gold", prompts[2],
                                 max_new_tokens=TRAFFIC_PARITY_TOKENS))
        sched.drain(max_rounds=120)
        return ([sched.requests[r].tokens_out for r in rids],
                sum(q.preemptions for q in sched.requests.values()),
                max(r.launches for r in sched.reports))

    roomy = ServingEngine(cfg, params, mesh=mesh, max_seqs=8,
                          max_blocks_per_seq=8, max_admit_pages=8,
                          double_buffer=True)
    ref_tokens, ref_preempted, _ = drive(roomy)
    tight = ServingEngine(cfg, params, mesh=mesh, max_seqs=2,
                          max_blocks_per_seq=8, num_slabs=2,
                          max_admit_pages=8, double_buffer=True,
                          spill_pages=8)
    tokens, preempted, max_launches = drive(tight)
    return {
        "tokens_match": tokens == ref_tokens,
        "preempted": int(preempted),
        "ref_preempted": int(ref_preempted),   # must be 0 (roomy engine)
        "max_launches_per_round": float(max_launches),
    }


def _traffic_mesh_child() -> None:
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()).reshape(MESH_SHAPE),
                ("data", "model"))
    print("TRAFFICPARITY:" + json.dumps(_traffic_parity(mesh=mesh)))


def _run_traffic_section(skip_mesh: bool) -> Dict:
    mt = _traffic_driver()
    legs = {}
    for pattern in TRAFFIC_PATTERNS:
        res = mt.run_traffic(pattern, rounds=TRAFFIC_ROUNDS, seed=0)
        legs[pattern] = {
            "max_launches_per_round": res.max_launches_per_round(),
            "mean_launches_per_round": float(np.mean(res.launches)),
            "submitted": res.submitted,
            "completed": res.completed,
            "preempted_requests": len(res.preempted_rids),
            "per_tenant": res.per_tenant,
        }
    section = {
        "rounds": TRAFFIC_ROUNDS,
        "tenants": {t.name: t.priority for t in mt.TENANTS},
        "legs": legs,
        "preempt_parity": _traffic_parity(),
        "mesh": None,
    }
    if skip_mesh:
        return section
    section["mesh"] = {
        "devices": int(np.prod(MESH_SHAPE)),
        "mesh_shape": list(MESH_SHAPE),
        "preempt_parity": _child_payload("--traffic-mesh-child",
                                         "TRAFFICPARITY:"),
    }
    return section


def _print_traffic(section: Dict) -> None:
    for pattern, leg in section["legs"].items():
        print(f"  {pattern:>8}: {leg['submitted']} arrived, "
              f"{leg['completed']} completed, "
              f"{leg['preempted_requests']} preempted, max "
              f"{leg['max_launches_per_round']:.1f} launches/round")
        for t, m in leg["per_tenant"].items():
            print(f"    {t:>6}: p50/p99 tok-lat "
                  f"{m['p50_token_latency_rounds']:.1f}/"
                  f"{m['p99_token_latency_rounds']:.1f} rounds  "
                  f"goodput {m['goodput_tok_s']:.1f} tok/s  "
                  f"preemptions {m['preemptions']}")
    p = section["preempt_parity"]
    print(f"  preempt parity: tokens match {p['tokens_match']} "
          f"({p['preempted']} demotions, max "
          f"{p['max_launches_per_round']:.1f} launches/round)")
    if section.get("mesh"):
        mp = section["mesh"]["preempt_parity"]
        print(f"  preempt parity (mesh, {section['mesh']['devices']} "
              f"devices): tokens match {mp['tokens_match']} "
              f"({mp['preempted']} demotions)")


def traffic_smoke(baseline_path: str = "BENCH_dispatch.json") -> int:
    """CI gate (``make bench-traffic``): FAIL (exit 1) if

    * any traffic leg's launches/round exceeds 1.0 under churn (the
      continuous-batching + preemption traffic must still drain each
      round as at most one fused launch),
    * no preemption actually happened (the leg stopped exercising the
      demotion path),
    * preempted-then-resumed sequences' greedy tokens diverge from the
      unpreempted run (CPU leg; the mesh leg runs under ``--skip-mesh``-
      less full benchmarks), or
    * a tenant's p99 token latency regresses > 1.5x against the
      committed ``BENCH_dispatch.json`` baseline (arrivals and the
      scheduler are deterministic at a fixed seed, so this is a real
      regression, not noise; skipped when no baseline has the section).
    """
    section = _run_traffic_section(skip_mesh=True)
    _print_traffic(section)
    ok = True
    for pattern, leg in section["legs"].items():
        if leg["max_launches_per_round"] > 1.0:
            print(f"FAIL: {pattern} leg hit "
                  f"{leg['max_launches_per_round']:.2f} launches/round "
                  "> 1.0 (churn or preemption now forces extra drains)")
            ok = False
        if leg["preempted_requests"] == 0:
            print(f"FAIL: {pattern} leg preempted nothing — the traffic "
                  "no longer exercises demotion")
            ok = False
    parity = section["preempt_parity"]
    if not parity["tokens_match"]:
        print("FAIL: preempted-then-resumed sequences' greedy tokens "
              "diverged from the unpreempted run")
        ok = False
    if parity["preempted"] == 0:
        print("FAIL: parity scenario demoted nothing")
        ok = False
    if parity["max_launches_per_round"] > 1.0:
        print(f"FAIL: preemption cost extra launches "
              f"({parity['max_launches_per_round']:.2f}/round > 1.0)")
        ok = False
    baseline = None
    if os.path.exists(baseline_path):
        try:
            with open(baseline_path) as f:
                baseline = json.load(f).get("serve_traffic")
        except (OSError, ValueError):
            baseline = None
    if baseline:
        for pattern, leg in section["legs"].items():
            base_leg = baseline.get("legs", {}).get(pattern)
            if not base_leg:
                continue
            for t, m in leg["per_tenant"].items():
                bm = base_leg["per_tenant"].get(t)
                if not bm:
                    continue
                base_p99 = bm["p99_token_latency_rounds"]
                if base_p99 > 0 and \
                        m["p99_token_latency_rounds"] > 1.5 * base_p99:
                    print(f"FAIL: {pattern}/{t} p99 token latency "
                          f"{m['p99_token_latency_rounds']:.1f} rounds "
                          f"> 1.5x baseline {base_p99:.1f}")
                    ok = False
    if ok:
        print("bench-traffic smoke OK: continuous batching + preemption "
              "hold 1.0 launches/round with bitwise resume parity")
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# mesh A/B — runs in a subprocess with 8 forced host devices (jax locks the
# device count at first init, so the parent process can't host it)
# ---------------------------------------------------------------------------

def _mesh_child() -> None:
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()).reshape(MESH_SHAPE),
                ("data", "model"))
    rows = [_bench_path(use_fused, batch, mesh=mesh, reps=MESH_REPS)
            for batch in MESH_BATCHES for use_fused in (True, False)]
    print("MESHROWS:" + json.dumps(rows))


def _host_rehearsals_skipped() -> bool:
    """The forced-host-device sections are CPU rehearsals of the mesh
    paths.  Off the CPU they are skipped, with one line saying so: a JAX
    child would contend with this process for its accelerator."""
    backend = jax.default_backend()
    if backend == "cpu":
        return False
    print(f"[bench_dispatch] backend {backend}: the forced-host-device "
          "mesh sections are CPU rehearsals and were skipped")
    return True


def _child_payload(flag: str, prefix: str):
    """Run this file in a fresh interpreter with 8 forced host devices
    and return the JSON payload of its ``prefix`` line.  A child that
    times out, fails or prints no payload raises: a failed section never
    turns into a ``null`` in the results."""
    n_dev = int(np.prod(MESH_SHAPE))
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env["PYTHONPATH"] = src + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), flag],
        env=env, capture_output=True, text=True, timeout=1200)
    lines = [l for l in out.stdout.splitlines() if l.startswith(prefix)]
    if out.returncode != 0 or not lines:
        raise RuntimeError(f"bench_dispatch child {flag} failed "
                           f"(rc={out.returncode}):\n{out.stderr[-2000:]}")
    return json.loads(lines[0][len(prefix):])


def _run_mesh_section() -> Dict:
    rows = _child_payload("--mesh-child", "MESHROWS:")
    f = [r for r in rows if r["path"] == "fused"]
    s = [r for r in rows if r["path"] == "seed"]
    return {
        "devices": int(np.prod(MESH_SHAPE)),
        "mesh_shape": list(MESH_SHAPE),
        "rows": rows,
        "summary": {
            "speedup": float(np.mean([r["us_per_flush"] for r in s]) /
                             np.mean([r["us_per_flush"] for r in f])),
            "launches_fused": float(np.mean(
                [r["launches_per_flush"] for r in f])),
            "launches_seed": float(np.mean(
                [r["launches_per_flush"] for r in s])),
        },
    }


def _autotune_section() -> Dict:
    """Summarize the committed TunedProfile for this backend (schema v9):
    which constants the autotuner picked and the measured win vs the
    hand-picked defaults.  ``profile`` is null when nothing is committed
    (run ``make bench-autotune`` to produce one)."""
    from repro.obs.autotune import load_profile, profile_path
    prof = load_profile()
    path = str(profile_path())
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if path.startswith(repo_root + os.sep):        # keep committed JSON
        path = os.path.relpath(path, repo_root)    # machine-independent
    if prof is None:
        return {"profile": None, "path": path}
    ratio = (prof.us_per_flush / prof.baseline_us_per_flush
             if prof.baseline_us_per_flush else None)
    out = prof.to_dict()
    out.pop("swept", None)           # full sweep rows live in the profile
    return {"profile": out, "path": path,
            "tuned_vs_default_us_ratio": ratio}


def run(skip_mesh: bool = False, skip_serve: bool = False) -> Dict:
    """Full benchmark: single-device dispatch A/B, the mesh leg, the
    serve_round/serve_traffic sections, the v8 bitwise/dedup legs, and
    the v9 autotune summary.  Returns the schema-v9 result dict."""
    rows = []
    for batch in BATCHES:
        for use_fused in (True, False):
            rows.append(_bench_path(use_fused, batch))
    small_f = [r for r in rows if r["path"] == "fused" and r["batch"] <= 8]
    small_s = [r for r in rows if r["path"] == "seed" and r["batch"] <= 8]
    speedup = (np.mean([r["us_per_flush"] for r in small_s]) /
               np.mean([r["us_per_flush"] for r in small_f]))
    return {
        "schema": "bench_dispatch/v9",
        "backend": jax.default_backend(),
        "block": list(BLOCK),
        "nblk": NBLK,
        "pools": ["k", "v"],
        "rows": rows,
        "summary": {"speedup_small_batch": float(speedup)},
        "mesh": None if skip_mesh else _run_mesh_section(),
        "serve_round": None if skip_serve else _run_serve_section(skip_mesh),
        "serve_traffic": None if skip_serve
        else _run_traffic_section(skip_mesh),
        "bitwise": _run_bitwise_section(),
        "dedup_admit": None if skip_serve else _run_dedup_section(),
        "autotune": _autotune_section(),
    }


def _print_rows(rows) -> None:
    for r in rows:
        print(f"{r['batch']:>6} {r['path']:>6} "
              f"{r['launches_per_flush']:>9.2f} {r['table_len']:>6} "
              f"{r['us_per_flush']:>10.1f} "
              f"{r['bytes_moved'] / 1e6:>9.1f}")


def _print_serve(section: Dict) -> None:
    for r in section["rows"]:
        print(f"  {r['path']:>14} {r['launches_admit_round']:>8.2f} "
              f"launches/admit-round {r['us_per_round']:>12.1f} us/round "
              f"({r['stage_promotions']} promotions, "
              f"{r['pool_bytes_resident'] / 1e6:.1f} MB resident, "
              f"{r['stage_capacity']} staging slots)")
    s = section["summary"]
    print(f"  round speedup {s['speedup']:.2f}x  (admit-round launches "
          f"{s['launches_fused']:.2f} fused vs {s['launches_seed']:.2f} "
          f"seed)")
    red = s["staging_memory_reduction"]
    print(f"  staging-ring memory reduction {red:.2f}x  "
          f"(tokens bitwise-identical: {s['ring_tokens_match']})")
    burst = section.get("burst_admission")
    if burst:
        for r in burst["rows"]:
            print(f"  burst {r['path']:>12} "
                  f"{r['launches_per_round']:>6.2f} launches/round "
                  f"{r['us_per_round']:>12.1f} us/round "
                  f"({r['stage_capacity']} staging slots)")
        b = burst["summary"]
        print(f"  burst ({burst['admits_per_round']} admits/round, "
              f"{burst['ring_pages']}-slot ring): "
              f"{b['launches_double']:.2f} double vs "
              f"{b['launches_single']:.2f} single launches/round "
              f"(tokens match: {b['tokens_match']})")
    fault = section.get("fault_recovery")
    if fault:
        fs = fault["summary"]
        print(f"  fault recovery ({', '.join(fault['injections'])}): "
              f"tokens match {fs['tokens_match']}, recovered in "
              f"{fs['rounds_to_recover']} round(s), {fs['evicted']} "
              f"evicted/re-admitted, post-recovery serve launches "
              f"<= {fs['max_launches_post_recovery']}, ckpt stream "
              f"active: {fs['ckpt_active']}")


def serve_smoke() -> int:
    """CI gate (``make bench-serve``): run the CPU serve_round legs and
    FAIL (exit 1) if the fused paths regress above 1.0 bulk-movement
    launch per round — the one-launch-per-flush invariant this repo is
    built around — or if ring staging stops matching the full twin's
    greedy tokens.  Since schema v8 it also gates the bitwise-opcode leg
    (fused must stay at 1.0 launch/flush with bit-identical pools vs the
    seed fan-out) and the dedup_admit leg (resident KV must shrink while
    greedy tokens stay bitwise-equal to the dedup-off twin at <= 1.0
    launches/round).  Returns the process exit code."""
    section = _run_serve_section(skip_mesh=True)
    _print_serve(section)
    ok = True
    for row in section["rows"]:
        if row["path"] in ("fused_staging", "ring_staging"):
            for key in ("launches_admit_round", "launches_per_round"):
                if row[key] > 1.0:
                    print(f"FAIL: {row['path']} {key} = {row[key]:.2f} "
                          "> 1.0 (serving round no longer drains as one "
                          "fused launch)")
                    ok = False
    if not section["summary"]["ring_tokens_match"]:
        print("FAIL: ring_staging greedy tokens diverged from "
              "fused_staging")
        ok = False
    burst = section["burst_admission"]
    for row in burst["rows"]:
        if row["path"] == "double_ring" and \
                row["launches_per_round"] > 1.0:
            print(f"FAIL: double-buffered ring burst rounds = "
                  f"{row['launches_per_round']:.2f} launches/round > 1.0 "
                  "(the shadow half no longer absorbs admission bursts)")
            ok = False
    if not burst["summary"]["tokens_match"]:
        print("FAIL: double-buffered burst greedy tokens diverged from "
              "single-buffered")
        ok = False
    fault = section["fault_recovery"]["summary"]
    if not fault["tokens_match"]:
        print("FAIL: fault-injected serve run's greedy tokens diverged "
              "from the failure-free run")
        ok = False
    if fault["rounds_to_recover"] > 2:
        print(f"FAIL: recovery took {fault['rounds_to_recover']} rounds "
              "to restore a clean serve flush (> 2)")
        ok = False
    if fault["max_launches_post_recovery"] > 1:
        print(f"FAIL: post-recovery serve rounds issue "
              f"{fault['max_launches_post_recovery']} bulk-movement "
              "launches (> 1.0/round)")
        ok = False
    bitwise = _run_bitwise_section()
    _print_bitwise(bitwise)
    bw = bitwise["summary"]
    if bw["launches_fused"] > 1.0:
        print(f"FAIL: fused bitwise flushes = {bw['launches_fused']:.2f} "
              "launches/flush > 1.0 (AND/OR/NOT rows no longer ride the "
              "fused table)")
        ok = False
    if not bw["bitwise_match"]:
        print("FAIL: fused bitwise pool bytes diverged from the seed "
              "fan-out path")
        ok = False
    dedup = _run_dedup_section()
    _print_dedup(dedup)
    if not dedup["tokens_match"]:
        print("FAIL: dedup-on-admit greedy tokens diverged from the "
              "dedup-off twin")
        ok = False
    if dedup["resident_reduction"] <= 0:
        print(f"FAIL: dedup_admit saved no resident KV bytes "
              f"(reduction = {dedup['resident_reduction']:.2%})")
        ok = False
    if dedup["max_launches_per_round"] > 1.0:
        print(f"FAIL: dedup serving rounds hit "
              f"{dedup['max_launches_per_round']:.2f} launches/round "
              "> 1.0")
        ok = False
    if ok:
        print("bench-serve smoke OK: fused serve rounds still drain as "
              "one launch")
    return 0 if ok else 1


def main() -> None:
    """CLI entry — see the module docstring for the output schema."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_dispatch.json")
    ap.add_argument("--skip-mesh", action="store_true",
                    help="skip the 8-device subprocess A/B sections")
    ap.add_argument("--skip-serve", action="store_true",
                    help="skip the serving-round A/B section")
    ap.add_argument("--serve-smoke", action="store_true",
                    help="CI gate: CPU serve_round legs only; exit 1 if "
                         "fused launches/round regress above 1.0")
    ap.add_argument("--traffic-smoke", action="store_true",
                    help="CI gate: serve_traffic legs only; exit 1 if "
                         "churn/preemption rounds exceed 1.0 launches, "
                         "resume parity breaks, or p99 regresses vs the "
                         "committed baseline")
    ap.add_argument("--mesh-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--serve-mesh-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--traffic-mesh-child", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.mesh_child:
        _mesh_child()
        return
    if args.serve_mesh_child:
        _serve_child()
        return
    if args.traffic_mesh_child:
        _traffic_mesh_child()
        return
    if args.serve_smoke:
        sys.exit(serve_smoke())
    if args.traffic_smoke:
        sys.exit(traffic_smoke())
    skip_mesh = args.skip_mesh or _host_rehearsals_skipped()
    result = run(skip_mesh=skip_mesh, skip_serve=args.skip_serve)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(f"{'batch':>6} {'path':>6} {'launches':>9} {'table':>6} "
          f"{'us/flush':>10} {'MB moved':>9}")
    _print_rows(result["rows"])
    print(f"\nsmall-batch (<=8) dispatch speedup: "
          f"{result['summary']['speedup_small_batch']:.2f}x")
    if result["mesh"]:
        m = result["mesh"]
        print(f"\nmesh ({m['devices']} host devices, "
              f"{'x'.join(map(str, m['mesh_shape']))}):")
        _print_rows(m["rows"])
        print(f"mesh flush speedup: {m['summary']['speedup']:.2f}x  "
              f"(launches/flush {m['summary']['launches_fused']:.2f} fused "
              f"vs {m['summary']['launches_seed']:.2f} seed)")
    if result["serve_round"]:
        sr = result["serve_round"]
        print(f"\nserve_round ({sr['arch']}, {sr['rounds']} rounds, "
              f"{sr['admit_rounds']} admissions):")
        _print_serve(sr)
        if sr["mesh"]:
            print(f"serve_round mesh ({sr['mesh']['devices']} host "
                  f"devices):")
            _print_serve(sr["mesh"])
    if result.get("serve_traffic"):
        st = result["serve_traffic"]
        print(f"\nserve_traffic ({st['rounds']} rounds, tenants "
              f"{st['tenants']}):")
        _print_traffic(st)
    if result.get("bitwise"):
        print("\nbitwise opcodes (AND/OR/NOT):")
        _print_bitwise(result["bitwise"])
    if result.get("dedup_admit"):
        print("\ndedup_admit:")
        _print_dedup(result["dedup_admit"])
    print(f"-> {args.out}")


if __name__ == "__main__":
    main()
