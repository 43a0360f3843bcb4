"""Readings the benchmark's settings are made from, in one process per
cell so the engine compiles once.  Not part of a benchmark run.

    python3 bench/calibrate.py sweep --workload <cell> --rates 2,3,4 --seconds 30
    python3 bench/calibrate.py seeds --workload <cell> --seeds 1,2,3 \\
        --control-seeds 1,2,3 --seconds 20

``sweep`` offers the cell's traffic at each rate in turn (open-loop mixes
only) and prints, per rate, what was offered and served and how the
backlog moved: the knee is the highest rate whose backlog does not grow.

``seeds`` makes a whole run per seed with a short window (weights and
tokens from that seed) and prints its comparison with the plain
reference; on the control seeds the int8 control stands in the
program's place, so its ``correct`` reads false.  The limit of
``max_logit_gap`` (and an MoE configuration's ``route_margin``) is set
from these readings (PERF.md gives them).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run, spec, stats  # noqa: E402


def sweep(cell, rates, seconds, seed):
    """Per rate: a fresh engine (the program leaks the dedup-registry
    blocks of a preempted request, PERF.md Open questions, so one engine
    carried across windows would serve later rates from a smaller pool),
    set up and driven by the very steps a run takes."""
    for rate in rates:
        st = run.set_up(cell, seed, log=lambda s: None,
                        load={"rate_per_s": rate}, t_from=run.now())
        drv, t_win = st.drv, st.t_win
        waiting0 = sum(1 for r in drv.req.values() if r["admitted"] is None)
        t_stop = run.drive(st, seconds)
        waiting1 = sum(1 for r in drv.req.values() if r["admitted"] is None)
        unfinished = sum(1 for r in drv.req.values() if r["done"] is None)
        due = [r for r in drv.req.values() if t_win <= r["due"] < t_stop]
        done = [r for r in drv.req.values()
                if r["done"] is not None and t_win < r["done"] <= t_stop]
        toks = sum(1 for r in drv.req.values() for t in r["times"]
                   if t_win < t <= t_stop)
        ttft = stats.ttft_samples(due, t_stop)
        itl = stats.inter_token_gaps(
            {k: r["times"] for k, r in drv.req.items()}, t_win, t_stop)
        win = t_stop - t_win
        print(json.dumps({
            "rate": rate, "window_s": win, "setup_s": st.setup_s,
            "due_per_s": len(due) / win, "done_per_s": len(done) / win,
            "tokens_per_s": toks / win,
            "waiting_start": waiting0, "waiting_end": waiting1,
            "unfinished_end": unfinished,
            "ttft_p50_ms": stats.percentile(ttft, 50) * 1e3,
            "ttft_p90_ms": stats.percentile(ttft, 90) * 1e3,
            "itl_p99_ms": stats.percentile(itl, 99) * 1e3,
            "preempted": sum(x["preempted"] for x in drv.rounds)}),
            flush=True)
        run.release(st)


def seeds(cell, devs, seed_list, control_seeds, seconds):
    """A whole run (``run.run_cell``, tracing off) per seed; for the
    control seeds the int8 reference stands in the program's place, and
    its ``correct`` has to come out false."""
    for seed in seed_list:
        ctl = seed in control_seeds
        result, cmp = run.run_cell(cell, seed, seconds, False, devs,
                                   log=lambda s: None, control=ctl)
        print(json.dumps({"seed": seed, "control": ctl,
                          "correct": result["correct"], **cmp}), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("sweep", "seeds"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args()
    cell = spec.resolve(spec.load_benchmark(), args.workload)
    devs = run.check_chips(cell["workload"]["chips"])
    import jax
    from repro.launch import serve
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    serve.setup_compile_cache()
    ints = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    if args.mode == "sweep":
        sweep(cell, [float(x) for x in args.rates.split(",")],
              args.seconds, args.seed)
    else:
        seeds(cell, devs, ints(args.seeds), set(ints(args.control_seeds)),
              args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
