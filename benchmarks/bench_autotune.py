"""Profiler-driven autotuner: sweep the engine's throughput constants,
persist the winners as a per-backend TunedProfile.

The engine's hand-picked constants — the command-table bucket set
(``cmdqueue.BUCKETS``), the fused kernel's overlapped-DMA toggle, the
serving staging-ring capacity, and the sharded jit-cache bound
(``fused_dispatch.MAX_DELTA_SIGNATURES``) — are exactly the knobs a MEF-
style experiment matrix tunes per machine.  This benchmark runs that
matrix against representative command streams:

* **flush matrix** — bucket set x overlap over mixed copy+zero flushes
  at several batch sizes (the same workload shape as
  ``bench_dispatch.py``), scoring each configuration by the mean of the
  per-batch median ``us_per_flush`` (measured with the shared obs
  stopwatch) and asserting the fused 1-launch-per-flush invariant holds
  under every configuration;
* **ring sweep** — staging-ring capacities over short serving runs
  (admissions + decode rounds through the real ``ServingEngine``),
  scoring by median ``us_per_round``;
* **delta-signature sweep** — ``MAX_DELTA_SIGNATURES`` candidates over
  repeated sharded-plan signature folds (the jit-cache bound only
  matters under a mesh; the sweep runs in the 8-host-device subprocess
  and is skipped with ``--quick``).

Winners are chosen by :func:`repro.obs.autotune.pick_winner`: a
candidate unseats the default only by beating it by a clear margin
(3%), so noise can never flip a committed constant.  The result is
saved as ``configs/tuned/<backend>.json`` — which
``RowCloneEngine``/``ServingEngine`` load at startup (explicit kwargs
always win; delete the file or set ``REPRO_NO_TUNED=1`` to opt out).

``--check`` is the CI gate wired into ``make bench-serve``: re-measure
the committed profile's configuration against the built-in defaults and
FAIL (exit 1) if the profile is slower than the defaults by more than
15% on the swept flush workload — a committed profile must never
regress the engine it claims to tune.

CLI: PYTHONPATH=src python benchmarks/bench_autotune.py
         [--out-dir DIR] [--quick] [--check] [--skip-ring] [--skip-mesh]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import RowCloneEngine, SubarrayAllocator
from repro.core import cmdqueue
from repro.kernels import fused_dispatch as fd
from repro.obs import metrics as obs_metrics
from repro.obs.autotune import (DEFAULT_MARGIN, TunedProfile, backend_key,
                                load_profile, pick_winner, save_profile)

BLOCK = (16, 2, 64)          # page x KVH x head_dim (bench_dispatch shape)
NBLK = 1024
NSLABS = 4

#: bucket-set candidates (first = the hand-picked default)
BUCKET_SETS: Tuple[Tuple[int, ...], ...] = (
    cmdqueue.DEFAULT_BUCKETS,
    (4, 16, 64, 256),
    (16, 64, 256, 1024),
    (8, 64, 512),
)
OVERLAPS = (True, False)
BATCHES = (4, 16, 64, 256)
REPS = 15

#: staging-ring candidates (None = the serving layer's policy derivation)
RING_CANDIDATES: Tuple[Optional[int], ...] = (None, 4, 8, 16)
RING_ROUNDS = 6
RING_ADMITS = 3

#: sharded jit-cache bound candidates (first = default)
DELTA_SIG_CANDIDATES = (fd.DEFAULT_MAX_DELTA_SIGNATURES, 4, 16)
MESH_SHAPE = (2, 4)
MESH_REPS = 8


def _mk_engine(overlap: bool) -> RowCloneEngine:
    alloc = SubarrayAllocator(NBLK, NSLABS, reserved_zero_per_slab=1)
    pools = {
        "k": jax.random.normal(jax.random.key(0), (NBLK,) + BLOCK,
                               jnp.float32),
        "v": jax.random.normal(jax.random.key(1), (NBLK,) + BLOCK,
                               jnp.float32),
    }
    return RowCloneEngine(pools, alloc, overlap=overlap)


def _flush_once(eng: RowCloneEngine, batch: int, round_i: int) -> None:
    """One mixed flush: ~3/4 copies, ~1/4 zero-inits, ids rotating per
    round (jit caches stay warm, data differs) — bench_dispatch's
    workload shape."""
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


def measure_flush_cfg(buckets: Sequence[int], overlap: bool,
                      batches: Sequence[int] = BATCHES,
                      reps: int = REPS) -> Dict:
    """Score one (bucket set, overlap) configuration: mean over batch
    sizes of the median flush wall-clock (us), with launch accounting.
    The bucket set installs process-wide for the measurement and is
    restored by the caller's sweep loop."""
    cmdqueue.set_buckets(buckets)
    per_batch: List[float] = []
    launches = 0
    flushes = 0
    try:
        for batch in batches:
            eng = _mk_engine(overlap)
            for r in range(3):                      # compile warmup
                _flush_once(eng, batch, r)
            times: List[float] = []
            l0 = eng.stats.launches
            for r in range(reps):
                with obs_metrics.Stopwatch() as sw:
                    _flush_once(eng, batch, 100 + r)
                    jax.block_until_ready(list(eng.pools.values()))
                times.append(sw.us)
            launches += eng.stats.launches - l0
            flushes += reps
            per_batch.append(obs_metrics.percentile(times, 50))
    finally:
        cmdqueue.set_buckets(None)
    return {
        "cfg": {"buckets": list(buckets), "overlap": bool(overlap)},
        "us_per_flush": float(np.mean(per_batch)),
        "us_per_batch": {str(b): round(v, 1)
                         for b, v in zip(batches, per_batch)},
        "launches_per_flush": launches / max(flushes, 1),
    }


def sweep_flush(batches: Sequence[int] = BATCHES,
                reps: int = REPS,
                bucket_sets: Sequence[Sequence[int]] = BUCKET_SETS,
                overlaps: Sequence[bool] = OVERLAPS) -> List[Dict]:
    """The bucket-set x overlap experiment matrix."""
    rows = []
    for buckets in bucket_sets:
        for overlap in overlaps:
            row = measure_flush_cfg(buckets, overlap, batches, reps)
            rows.append(row)
            print(f"  flush buckets={list(buckets)!s:>20} "
                  f"overlap={overlap!s:>5}: "
                  f"{row['us_per_flush']:>9.1f} us/flush "
                  f"({row['launches_per_flush']:.2f} launches)")
    return rows


def measure_ring(ring: Optional[int], rounds: int = RING_ROUNDS,
                 admits: int = RING_ADMITS) -> Dict:
    """Score one staging-ring capacity over a short serving run (admit a
    prompt for the first ``admits`` rounds, decode every round)."""
    from repro.configs import get_config
    from repro.launch.serve import ServingEngine
    from repro.models import build_model, split_params
    cfg = get_config("llama3.2-3b").reduced()
    model = build_model(cfg)
    params, _ = split_params(model.init_params(jax.random.key(0)))
    eng = ServingEngine(cfg, params, max_seqs=8, max_blocks_per_seq=16,
                        max_admit_pages=ring, adaptive_ring=False)
    rng = np.random.default_rng(0)
    times: List[float] = []
    for r in range(rounds):
        with obs_metrics.Stopwatch() as sw:
            if r < admits:
                eng.add_request(rng.integers(2, cfg.vocab_size, size=24)
                                .astype(np.int32))
            eng.decode_round()
            jax.block_until_ready([eng.engine.pools["k"],
                                   eng.engine.pools["v"]])
        times.append(sw.us)
    meas = times[2:] if len(times) > 2 else times   # drop compile rounds
    return {
        "cfg": {"ring": ring},
        "us_per_flush": float(obs_metrics.percentile(meas, 50)),
        "stage_capacity": int(eng.engine.stage_capacity),
    }


def sweep_ring(rounds: int = RING_ROUNDS,
               candidates: Sequence[Optional[int]] = RING_CANDIDATES
               ) -> List[Dict]:
    rows = []
    for ring in candidates:
        row = measure_ring(ring, rounds=rounds)
        rows.append(row)
        print(f"  ring={str(ring):>6}: {row['us_per_flush']:>10.1f} "
              f"us/round ({row['stage_capacity']} slots)")
    return rows


# ---------------------------------------------------------------------------
# delta-signature sweep — sharded plans in the 8-host-device subprocess
# ---------------------------------------------------------------------------

def _delta_child() -> None:
    """Child process (8 forced host devices): time mesh flushes whose
    cross-slab delta signatures rotate, for each MAX_DELTA_SIGNATURES
    candidate — a small bound folds distant deltas into one compiled
    collective (fewer compiles, more padding); a large bound compiles
    more variants."""
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()).reshape(MESH_SHAPE),
                ("data", "model"))
    rows = []
    for cand in DELTA_SIG_CANDIDATES:
        fd.set_max_delta_signatures(cand)
        try:
            alloc = SubarrayAllocator(NBLK, NSLABS, reserved_zero_per_slab=1)
            pools = {
                "k": jax.random.normal(jax.random.key(0), (NBLK,) + BLOCK,
                                       jnp.float32),
                "v": jax.random.normal(jax.random.key(1), (NBLK,) + BLOCK,
                                       jnp.float32),
            }
            eng = RowCloneEngine(pools, alloc, mesh=mesh)
            shard = NBLK // int(np.prod(MESH_SHAPE))
            for r in range(2):                      # warmup compiles
                _flush_once(eng, 16, r)
            times = []
            for r in range(MESH_REPS):
                with obs_metrics.Stopwatch() as sw:
                    # rotate a cross-slab pair per rep so the plan's
                    # delta signature changes and the bound matters
                    s = 1 + r % (shard - 1)
                    d = NBLK - 1 - r % (shard - 1)
                    eng.alloc.mark_written([s])
                    eng.memcopy([(s, d)])
                    jax.block_until_ready(list(eng.pools.values()))
                times.append(sw.us)
            rows.append({"cfg": {"max_delta_signatures": cand},
                         "us_per_flush":
                         obs_metrics.percentile(times, 50)})
        finally:
            fd.set_max_delta_signatures(None)
    print("DELTAROWS:" + json.dumps(rows))


def sweep_delta_signatures() -> List[Dict]:
    """Run the delta-signature sweep in a fresh 8-host-device process
    (jax pins the device count at first init).  Raises when it fails."""
    n_dev = int(np.prod(MESH_SHAPE))
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env["PYTHONPATH"] = src + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--delta-child"],
        env=env, capture_output=True, text=True, timeout=900)
    lines = [l for l in out.stdout.splitlines()
             if l.startswith("DELTAROWS:")]
    if out.returncode != 0 or not lines:
        raise RuntimeError(f"delta-signature sweep child failed "
                           f"(rc={out.returncode}):\n{out.stderr[-2000:]}")
    rows = json.loads(lines[0][len("DELTAROWS:"):])
    for r in rows:
        print(f"  max_delta_signatures={r['cfg']['max_delta_signatures']:>3}"
              f": {r['us_per_flush']:>10.1f} us/flush")
    return rows


# ---------------------------------------------------------------------------
# tune + check
# ---------------------------------------------------------------------------

def tune(out_dir: Optional[str] = None, quick: bool = False,
         skip_ring: bool = False, skip_mesh: bool = False) -> TunedProfile:
    """Run the sweeps, pick winners (margin rule), save and reload the
    per-backend profile.  Returns the saved :class:`TunedProfile`."""
    prev_no_tuned = os.environ.get("REPRO_NO_TUNED")
    os.environ["REPRO_NO_TUNED"] = "1"      # sweeps measure raw configs
    try:
        backend = backend_key()
        batches = (4, 32) if quick else BATCHES
        reps = 5 if quick else REPS
        bucket_sets = BUCKET_SETS[:2] if quick else BUCKET_SETS
        print(f"[bench_autotune] backend={backend} flush matrix "
              f"({len(bucket_sets)} bucket sets x {len(OVERLAPS)} overlap)")
        flush_rows = sweep_flush(batches, reps, bucket_sets)
        default_cfg = {"buckets": list(cmdqueue.DEFAULT_BUCKETS),
                       "overlap": True}
        flush_win = pick_winner(flush_rows, default_cfg)
        flush_default = next(r for r in flush_rows
                             if r["cfg"] == default_cfg)
        swept: Dict = {
            "flush": {"rows": flush_rows,
                      "winner": flush_win["cfg"],
                      "margin": DEFAULT_MARGIN},
        }
        ring: Optional[int] = None
        if not skip_ring:
            print("[bench_autotune] staging-ring sweep")
            ring_rows = sweep_ring(rounds=4 if quick else RING_ROUNDS)
            ring_win = pick_winner(ring_rows, {"ring": None})
            ring = ring_win["cfg"]["ring"]
            swept["ring"] = {"rows": ring_rows, "winner": ring_win["cfg"]}
        delta = fd.DEFAULT_MAX_DELTA_SIGNATURES
        if not (quick or skip_mesh):
            print("[bench_autotune] delta-signature sweep (mesh child)")
            delta_rows = sweep_delta_signatures()
            d_win = pick_winner(
                delta_rows,
                {"max_delta_signatures": fd.DEFAULT_MAX_DELTA_SIGNATURES})
            delta = int(d_win["cfg"]["max_delta_signatures"])
            swept["delta_signatures"] = {"rows": delta_rows,
                                         "winner": d_win["cfg"]}
        profile = TunedProfile(
            backend=backend,
            buckets=tuple(flush_win["cfg"]["buckets"]),
            overlap=bool(flush_win["cfg"]["overlap"]),
            max_delta_signatures=delta,
            ring_capacity=ring,
            us_per_flush=float(flush_win["us_per_flush"]),
            baseline_us_per_flush=float(flush_default["us_per_flush"]),
            swept=swept)
    finally:
        if prev_no_tuned is None:
            os.environ.pop("REPRO_NO_TUNED", None)
        else:
            os.environ["REPRO_NO_TUNED"] = prev_no_tuned
    path = save_profile(profile, directory=out_dir)
    print(f"[bench_autotune] wrote {path}")
    # reload through the startup path — the engine's "profile loaded"
    # breadcrumb should print right here
    loaded = load_profile(directory=out_dir)
    assert loaded is not None and loaded.backend == profile.backend
    return profile


def check(margin: float = 1.15, quick: bool = True) -> int:
    """CI gate: the committed profile must not be slower than the
    built-in defaults by more than ``margin`` on the swept flush
    workload.  Exit 0 when no profile is committed (nothing to gate).

    Replays the SAME batch sizes the full tune scored (``BATCHES``) —
    a bucket set is tuned for that batch mix, and measuring a different
    mix (e.g. only small batches, where coarse buckets over-pad) would
    flag a genuinely faster profile as a regression.  ``quick`` only
    drops the rep count."""
    prof = load_profile()
    if prof is None:
        print("[bench_autotune] no committed profile for backend "
              f"{backend_key()!r}: nothing to check")
        return 0
    batches = BATCHES
    reps = 5 if quick else REPS
    prev_no_tuned = os.environ.get("REPRO_NO_TUNED")
    os.environ["REPRO_NO_TUNED"] = "1"
    try:
        default_row = measure_flush_cfg(cmdqueue.DEFAULT_BUCKETS, True,
                                        batches, reps)
        tuned_row = measure_flush_cfg(prof.buckets, prof.overlap,
                                      batches, reps)
    finally:
        if prev_no_tuned is None:
            os.environ.pop("REPRO_NO_TUNED", None)
        else:
            os.environ["REPRO_NO_TUNED"] = prev_no_tuned
    d, t = default_row["us_per_flush"], tuned_row["us_per_flush"]
    print(f"[bench_autotune] check: defaults {d:.1f} us/flush, "
          f"tuned profile {t:.1f} us/flush ({t / d:.2f}x)")
    if t > d * margin:
        print(f"FAIL: committed tuned profile is {t / d:.2f}x slower "
              f"than the defaults (> {margin:.2f}x) — retune or delete "
              "configs/tuned/" + prof.backend + ".json")
        return 1
    print("bench-autotune check OK: committed profile does not regress "
          "the defaults")
    return 0


def main() -> None:
    """CLI entry — sweep and persist (default), or ``--check`` gate."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--out-dir", default=None,
                    help="profile directory (default configs/tuned/, or "
                         "$REPRO_TUNED_DIR)")
    ap.add_argument("--quick", action="store_true",
                    help="tiny matrix/reps (smoke tests)")
    ap.add_argument("--skip-ring", action="store_true",
                    help="skip the serving staging-ring sweep")
    ap.add_argument("--skip-mesh", action="store_true",
                    help="skip the 8-device delta-signature sweep")
    ap.add_argument("--check", action="store_true",
                    help="CI gate: committed profile must not regress "
                         "the defaults (exit 1 on regression)")
    ap.add_argument("--delta-child", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.delta_child:
        _delta_child()
        return
    if args.check:
        sys.exit(check())
    skip_mesh = args.skip_mesh
    if not skip_mesh and jax.default_backend() != "cpu":
        # a JAX child would contend with this process for the chip
        print(f"[bench_autotune] backend {jax.default_backend()}: the "
              "forced-host-device delta-signature sweep is a CPU "
              "rehearsal and was skipped")
        skip_mesh = True
    prof = tune(out_dir=args.out_dir, quick=args.quick,
                skip_ring=args.skip_ring, skip_mesh=skip_mesh)
    print(f"[bench_autotune] winner: buckets={list(prof.buckets)} "
          f"overlap={prof.overlap} ring={prof.ring_capacity} "
          f"max_delta_signatures={prof.max_delta_signatures} "
          f"({prof.us_per_flush:.1f} us/flush vs "
          f"{prof.baseline_us_per_flush:.1f} default)")


if __name__ == "__main__":
    main()
