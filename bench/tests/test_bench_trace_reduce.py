"""The reduction from a device trace to per-layer numbers: a hand-made
trace with known answers, and a slice of a real trace recorded on a TPU
v5e (yi-6b serving chat traffic) whose numbers are pinned."""
import gzip
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

from bench import trace_reduce as tr  # noqa: E402
from bench import spec  # noqa: E402

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / \
    "trace_yi6b_chat_v5e.json.gz"
MS = 1_000_000   # ns


def handmade():
    """Window 0-100 ms.  Device: decode 10-40 (ops 10-25, 27-40), drain
    45-47, decode 60-90.  Host (driving thread): a round 6-95 with an
    argmax 40-45 and an np.asarray wait 47-60 inside it."""
    return {
        "devices": {"0": {
            tr.MODULES: [["jit__decode_fn(7)", 10 * MS, 30 * MS],
                         ["jit__fused_dispatch_jit(3)", 45 * MS, 2 * MS],
                         ["jit__decode_fn(7)", 60 * MS, 30 * MS]],
            tr.OPS: [["%fusion.1", 10 * MS, 15 * MS],
                     ["%fusion.2", 27 * MS, 13 * MS],
                     ["%custom-call", 45 * MS, 2 * MS],
                     ["%fusion.1", 60 * MS, 30 * MS]],
        }},
        "host": [
            [tr.WINDOW_SPAN, 0.0, 100 * MS, "main"],
            ["engine.decode_round", 6 * MS, 89 * MS, "main"],
            ["engine.argmax", 40 * MS, 5 * MS, "main"],
            ["np.asarray(jax.Array)", 47 * MS, 13 * MS, "main"],
            ["Transpose", 0.0, 100 * MS, "runtime"],   # another thread
        ],
    }


def test_handmade_trace_known_answers():
    red = tr.reduce(handmade())
    assert red["window_s"] == pytest.approx(0.100)
    # busy: 15 + 13 + 2 + 30 ms (the 25-27 hole inside a program is idle)
    assert red["busy_s"] == pytest.approx(0.060)
    assert red["programs"]["jit__decode_fn"] == {
        "count": 2, "device_s": pytest.approx(0.060)}
    assert tr.program_time(red, "fused_dispatch") == (
        1, pytest.approx(0.002))
    gaps = {round(s / MS): (round(d * 1e3, 6), n) for s, d, n in red["gaps"]}
    assert gaps == {0: (10.0, "no host span"),       # 0-10, midpoint 5
                    25: (2.0, "engine.decode_round"),
                    40: (5.0, "engine.argmax"),
                    47: (13.0, "np.asarray(jax.Array)"),
                    90: (10.0, "engine.decode_round")}
    assert red["idle_by_span"]["engine.decode_round"] == pytest.approx(0.012)
    bd = tr.breakdown(red)
    assert bd["device_ops"][0] == ["jit__decode_fn", pytest.approx(0.060)]
    assert bd["idle_gaps"][0] == ["np.asarray(jax.Array)",
                                  pytest.approx(0.013)]


def test_explicit_window_clips_events():
    red = tr.reduce(handmade(), window=(20 * MS, 50 * MS))
    assert red["window_s"] == pytest.approx(0.030)
    assert red["busy_s"] == pytest.approx(0.005 + 0.013 + 0.002)
    assert red["programs"]["jit__decode_fn"]["device_s"] == \
        pytest.approx(0.020)


def test_names_are_normalised():
    assert tr.program_name("jit__decode_fn(14393292661423940431)") == \
        "jit__decode_fn"
    assert tr.op_name("%copy-start = (s32[16,16]) copy-start(%t)") == \
        "%copy-start"


def test_recorded_chip_trace_pinned():
    """150 ms of a traced yi-6b chat window on one v5e: five decode steps,
    one prefill, one drain; the idle time falls in the logits copy."""
    with gzip.open(FIXTURE, "rt") as f:
        trace = json.load(f)
    red = tr.reduce(trace)
    assert red["window_s"] == pytest.approx(0.150)
    assert red["busy_s"] == pytest.approx(0.124496769, rel=1e-9)
    n, t = tr.program_time(red, "decode_fn")
    assert (n, t) == (5, pytest.approx(0.119458925, rel=1e-9))
    assert tr.program_time(red, "prefill_stage_fn") == (
        1, pytest.approx(0.004998276, rel=1e-9))
    assert tr.program_time(red, "fused_dispatch") == (
        1, pytest.approx(3.8214e-05, rel=1e-9))
    top = max(red["idle_by_span"].items(), key=lambda x: x[1])
    assert top[0] == "np.asarray(jax.Array)"
    assert top[1] == pytest.approx(0.022895759, rel=1e-6)
    assert sum(red["idle_by_span"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-9)


def test_metric_readers_on_the_recorded_trace():
    with gzip.open(FIXTURE, "rt") as f:
        red = tr.reduce(json.load(f))
    m = spec.load_json(spec.BENCH_DIR / "configs" / "yi-6b.json")
    peaks = spec.load_json(spec.BENCH_DIR / "peaks.json")["kinds"][
        "TPU v5 lite"]
    run = {"trace": red, "model": m, "page": 64, "peaks": peaks,
           "host": {"queue_wait_s": [0.1, 0.2, 0.3],
                    "traced_prefill_tokens": 384,
                    "traced_drain_rows": {"cross_pool_copy": 12.0},
                    "flops": 1e12, "rounds_s": 1.0}}

    def read(name):
        return spec.metric_reader(name)(run)

    assert read("decode_step_ms") == pytest.approx(119.458925 / 5)
    assert read("prefill_ms_per_ktok") == pytest.approx(
        4.998276 / 384 * 1e3)
    blk = 32 * 64 * 4 * 128 * 2
    assert read("drain_roofline") == pytest.approx(
        12 * 2 * blk / 3.8214e-05 / 819e9 * 100)
    assert read("idle_share.open") == pytest.approx(
        (1 - 0.124496769 / 0.150) * 100)
    assert read("mfu.closed") == pytest.approx(1e12 / 197e12 * 100)
    assert read("queue_wait_p90_ms") == pytest.approx(280.0)
    run["host"]["traced_drain_rows"] = {}
    assert read("drain_roofline") is None      # nothing to read: silent


def test_gap_readers_read_every_gap_of_the_window():
    """``itl_p95_ms.open`` and ``stall_gap_share`` read the window's
    inter-token gaps from the host clock; with none they read nothing."""
    gaps = [0.027] * 95 + [0.055] * 5
    run = {"host": {"itl_s": gaps}}

    def read(name):
        return spec.metric_reader(name)(run)

    assert read("itl_p95_ms.open") == pytest.approx(
        (0.027 + (0.055 - 0.027) * 0.05) * 1e3)
    assert read("stall_gap_share") == pytest.approx(5.0)
    run["host"]["itl_s"] = gaps[:-1]
    assert read("stall_gap_share") == pytest.approx(4 / 99 * 100)
    assert read("itl_p95_ms.open") == pytest.approx(27.0)
    run["host"]["itl_s"] = []
    assert read("itl_p95_ms.open") is None
    assert read("stall_gap_share") is None
