"""The round's stage reduction (``bench/round_trace.py``) on hand-made
traces with known answers: the host-device offset from launch pairs,
idle gaps split by overlap over the program's spans, the driving loop's
idle wait kept apart from time no span covers, and scoped device time."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

from bench import round_trace as rt  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402

MS = 1_000_000   # ns


def launches():
    """Decode launched at 10 and 50 ms (the profiler records each call
    twice, nested), a drain at 40 ms; the device's clock reads 0.7 ms
    early for the first decode, 0.5 ms for the second, 0.2 ms late for
    the drain."""
    return {
        "devices": {"0": {tr.MODULES: [
            ["jit__decode_fn(1)", 9.3 * MS, 20 * MS],
            ["jit__fused_dispatch_jit(2)", 40.2 * MS, 0.1 * MS],
            ["jit__decode_fn(1)", 49.5 * MS, 20 * MS]]}},
        "host": [
            [tr.WINDOW_SPAN, 0.0, 100 * MS, "main"],
            ["PjitFunction(_decode_fn)", 10 * MS, 0.4 * MS, "main"],
            ["PjitFunction(_decode_fn)", 10.01 * MS, 0.3 * MS, "main"],
            ["PjitFunction(_fused_dispatch_jit)", 40 * MS, 0.3 * MS, "main"],
            ["PjitFunction(_decode_fn)", 50 * MS, 0.4 * MS, "main"],
            ["PjitFunction(_decode_fn)", 50.01 * MS, 0.3 * MS, "main"],
            ["PjitFunction(_decode_fn)", 30 * MS, 0.4 * MS, "other"],
        ],
    }


def test_offset_recovered_from_launch_pairs():
    trace = launches()
    assert sorted(rt.launch_pairs(trace)) == [
        (10 * MS, 9.3 * MS), (40 * MS, 40.2 * MS), (50 * MS, 49.5 * MS)]
    aligned, clock = rt.align(trace)
    assert clock == {"offset_ms": pytest.approx(0.7), "pairs": 3}
    starts = [s for _, s, _ in aligned["devices"]["0"][tr.MODULES]]
    assert starts == pytest.approx([10 * MS, 40.9 * MS, 50.2 * MS])
    assert trace["devices"]["0"][tr.MODULES][0][1] == 9.3 * MS  # a copy


def test_queued_programs_pair_in_launch_order():
    """A program that queues behind a long one starts long after its
    call; the next call of it must not take its execution (which starts
    shortly before that call) and read a false offset."""
    trace = {
        "devices": {"0": {tr.MODULES: [
            ["jit__prefill_stage_fn(1)", 0.5 * MS, 44 * MS],
            ["jit_squeeze(2)", 45 * MS, 0.01 * MS],
            ["jit_squeeze(2)", 90 * MS, 0.01 * MS]]}},
        "host": [
            [tr.WINDOW_SPAN, 0.0, 100 * MS, "main"],
            ["PjitFunction(_prefill_stage_fn)", 0.0, 0.4 * MS, "main"],
            ["PjitFunction(squeeze)", 1 * MS, 0.1 * MS, "main"],
            ["PjitFunction(squeeze)", 51 * MS, 0.1 * MS, "main"],
        ],
    }
    assert rt.launch_pairs(trace) == [
        (0.0, 0.5 * MS), (1 * MS, 45 * MS), (51 * MS, 90 * MS)]
    assert rt.align(trace)[1] == {"offset_ms": 0.0, "pairs": 3}


def test_no_offset_when_every_program_follows_its_launch():
    trace = launches()
    for ev in trace["devices"]["0"][tr.MODULES]:
        ev[1] += 1 * MS
    _, clock = rt.align(trace)
    assert clock == {"offset_ms": 0.0, "pairs": 3}


def round_trace():
    """Window 0-100 ms.  Device: decode 0-10, 20-30, 60-70 ms.  Driving
    thread: a round 0-30 whose logits fetch (2-13) ends inside the gap
    10-20, then the decode round (to 16), the report (16-18) and the next
    round's argmax (18-21); the driving loop sleeps 32-45 for want of
    work and works 50-58; nothing is spanned 30-32, 45-50, 58-100."""
    return {
        "devices": {"0": {
            tr.MODULES: [["jit__decode_fn(1)", 0.0, 10 * MS],
                         ["jit__decode_fn(1)", 20 * MS, 10 * MS],
                         ["jit__decode_fn(1)", 60 * MS, 10 * MS]],
            tr.OPS: [["%fusion", 0.0, 10 * MS],
                     ["%fusion", 20 * MS, 10 * MS],
                     ["%fusion", 60 * MS, 10 * MS]],
        }},
        "host": [
            [tr.WINDOW_SPAN, 0.0, 100 * MS, "main"],
            ["sched.step#round=7#", 0.0, 30 * MS, "main"],
            ["engine.decode_round", 0.0, 16 * MS, "main"],
            ["engine.logits_fetch", 2 * MS, 11 * MS, "main"],
            ["sched.report", 16 * MS, 2 * MS, "main"],
            ["engine.argmax", 18 * MS, 3 * MS, "main"],
            ["bench.idle_wait", 32 * MS, 13 * MS, "main"],
            ["bench.loop", 50 * MS, 8 * MS, "main"],
            ["np.asarray(jax.Array)", 9 * MS, 4 * MS, "main"],  # JAX's own
            ["engine.table_upload", 10 * MS, 30 * MS, "runtime"],
        ],
    }


def test_gap_split_by_overlap_not_midpoint():
    trace = round_trace()
    # the old reduction hands the whole 10-20 gap to the span at 15 ms
    old = {round(s / MS): n for s, _, n in tr.reduce(trace)["gaps"]}
    assert old[10] == "engine.decode_round"
    got = rt.idle_by_stage(trace)
    by = {k: v * 1e3 for k, v in got["by_stage"].items()}
    assert by == {"engine.logits_fetch": pytest.approx(3.0),
                  "engine.decode_round": pytest.approx(3.0),
                  "sched.report": pytest.approx(2.0),
                  "engine.argmax": pytest.approx(2.0),
                  "untraced": pytest.approx(2 + 5 + 2 + 30),
                  "bench.idle_wait": pytest.approx(13.0),
                  "bench.loop": pytest.approx(8.0)}
    assert got["idle_s"] == pytest.approx(0.070)
    assert got["spans"] == ["bench.idle_wait", "bench.loop",
                            "engine.argmax", "engine.decode_round",
                            "engine.logits_fetch", "sched.report",
                            "sched.step"]


def test_idle_wait_and_untraced_kept_apart_per_round():
    got = rt.idle_by_stage(round_trace())
    # rounds 0-20 (idle 10-20) and 20-60 (idle 30-60)
    assert got["rounds"] == 2
    assert got["round_ms"]["bench.idle_wait"] == pytest.approx(6.5)
    assert got["round_ms"]["untraced"] == pytest.approx(4.5)
    assert got["round_ms"]["engine.logits_fetch"] == pytest.approx(1.5)
    # host stages: 10 ms in the first round, 8 (bench.loop) in the second
    assert got["round_host_ms"] == pytest.approx(9.0)


def test_stage_reduction_aligns_first():
    trace = round_trace()
    trace["host"].append(["PjitFunction(_decode_fn)", 21 * MS, 0.3 * MS,
                          "main"])
    got = rt.reduce(trace)
    assert got["offset_ms"] == pytest.approx(1.0) and got["pairs"] == 1
    # the device now idles 0-1 ms too, under the round's decode
    assert got["by_stage"]["engine.decode_round"] == pytest.approx(0.004)
    assert got["decode_attention"] is None


def test_scope_time_counts_nested_ops_once():
    trace = round_trace()
    ops = trace["devices"]["0"][tr.OPS]
    ops += [["%dot", 2 * MS, 3 * MS], ["%dot.inner", 3 * MS, 1 * MS],
            ["%dot", 22 * MS, 2 * MS], ["%mlp", 25 * MS, 4 * MS]]
    trace[rt.OP_SCOPES] = {"0": {
        "names": ["jit(_decode_fn)/while/body/decode_attention/dot_general",
                  "jit(_decode_fn)/while/body/mlp"],
        "index": [-1, -1, -1, 0, 0, 0, 1]}}
    got = rt.scope_time(trace, "decode_attention")
    assert got == {"count": 3, "device_s": pytest.approx(0.005)}
    assert rt.scope_time(trace, "prefill_attention") is None
    del trace[rt.OP_SCOPES]
    assert rt.scope_time(trace, "decode_attention") is None


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 7 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 8 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 9 offset_ps: 8000000 duration_ps: 1000000 } }
  event_metadata { key: 7 value { id: 7 name: "%fusion.1 = f32[2] fusion(%p)"
    stats { metadata_id: 3
            str_value: "jit(_decode_fn)/while/body/decode_attention/dot:" } } }
  event_metadata { key: 8 value { id: 8 name: "%fusion.2 = f32[2] fusion(%q)"
    stats { metadata_id: 4 ref_value: 5 } } }
  event_metadata { key: 9 value { id: 9 name: "%copy.3 = f32[2] copy(%r)" } }
  stat_metadata { key: 3 value { id: 3 name: "tf_op" } }
  stat_metadata { key: 4 value { id: 4 name: "tf_op" } }
  stat_metadata { key: 5 value { id: 5 name: "jit(_decode_fn)/mlp/dot:" } }
}
planes { id: 2 name: "/host:CPU" }
"""


def test_op_scopes_read_from_the_event_metadata(tmp_path):
    """A TPU trace keeps each operation's scope path in its event
    metadata's ``tf_op`` stat, as a string or a reference to a stat
    name; ``load`` gives it per operation event, -1 where there is
    none, and keeps ``trace_reduce.load``'s events as they are."""
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    assert rt.op_scopes(str(path)) == {"/device:TPU:0": {
        "%fusion.1 = f32[2] fusion(%p)":
            "jit(_decode_fn)/while/body/decode_attention/dot:",
        "%fusion.2 = f32[2] fusion(%q)": "jit(_decode_fn)/mlp/dot:"}}
    trace = rt.load(str(path))
    assert [e[0] for e in trace["devices"]["0"][tr.OPS]] == [
        "%fusion.1", "%fusion.2", "%copy.3"]
    assert {k: v for k, v in trace.items() if k != rt.OP_SCOPES} == \
        tr.load(str(path))
    assert trace[rt.OP_SCOPES] == {"0": {
        "names": ["jit(_decode_fn)/while/body/decode_attention/dot:",
                  "jit(_decode_fn)/mlp/dot:"],
        "index": [0, 1, -1]}}


def test_new_readers_silent_without_program_spans():
    """A trace of a program that spans no round (as before the round
    spans existed) leaves the round's four numbers unread."""
    trace = round_trace()
    got = rt.metrics(rt.reduce(trace))
    assert got["round_host_ms"] == pytest.approx(9.0)
    assert got["logits_fetch_ms"] == pytest.approx(1.5)
    assert got["decode_attn_ms"] is None
    trace["host"] = [h for h in trace["host"]
                     if h[0] in (tr.WINDOW_SPAN, "bench.loop",
                                 "bench.idle_wait")]
    assert rt.metrics(rt.reduce(trace)) == dict.fromkeys(
        ("round_host_ms", "logits_fetch_ms", "table_upload_ms",
         "decode_attn_ms"))


def test_offset_on_the_first_recorded_chip_trace():
    """The v5e trace recorded before the program spanned its round: its
    drain and five decode programs start 0.64-0.80 ms before the host
    calls that launched them; aligned, none does."""
    import gzip
    import json
    fixture = pathlib.Path(__file__).parent / "fixtures" / \
        "trace_yi6b_chat_v5e.json.gz"
    with gzip.open(fixture, "rt") as f:
        trace = json.load(f)
    pairs = rt.launch_pairs(trace)
    assert len(pairs) == 6
    assert min(h - d for h, d in pairs) == pytest.approx(642_147.0)
    aligned, clock = rt.align(trace)
    assert clock == {"offset_ms": pytest.approx(0.801328), "pairs": 6}
    assert all(h <= d + clock["offset_ms"] * 1e6 + 1e-3 for h, d in pairs)


def test_recorded_round_trace_pinned():
    """150 ms of a traced yi-6b chat window on one v5e, recorded with the
    program's round spans and its decode attention scope, under a driving
    loop that spanned its own work (``bench.loop``): four decode rounds
    begin inside it, with one prefill and one drain.  The device
    clock reads ~1 ms early; every idle gap falls under a program span
    but 43 µs."""
    import gzip
    import json
    fixture = pathlib.Path(__file__).parent / "fixtures" / \
        "trace_yi6b_chat_rounds_v5e.json.gz"
    with gzip.open(fixture, "rt") as f:
        trace = json.load(f)
    st = rt.reduce(trace)
    assert st["offset_ms"] == pytest.approx(0.96005)
    assert st["pairs"] == 8
    assert st["rounds"] == 3
    assert st["idle_s"] == pytest.approx(0.021299934, rel=1e-6)
    assert st["by_stage"]["untraced"] == pytest.approx(4.3321e-05, rel=1e-3)
    top = max(st["by_stage"].items(), key=lambda x: x[1])
    assert top == ("engine.logits_fetch", pytest.approx(0.008113219))
    got = rt.metrics(st)
    assert got["round_host_ms"] == pytest.approx(4.240884, rel=1e-6)
    assert got["logits_fetch_ms"] == pytest.approx(2.048595, rel=1e-6)
    assert got["table_upload_ms"] == pytest.approx(0.87812, rel=1e-6)
    assert got["decode_attn_ms"] == pytest.approx(3.357236, rel=1e-6)
    # the accepted reduction reads it as before
    red = tr.reduce(trace)
    assert red["window_s"] == pytest.approx(0.150)
    assert red["busy_s"] == pytest.approx(0.128700066, rel=1e-9)
    assert tr.program_time(red, "prefill_stage_fn") == (
        1, pytest.approx(0.028957131, rel=1e-9))


def test_command_line_reads_a_kept_trace(tmp_path, capsys):
    """``python3 -m bench.round_trace`` on a trace kept by ``run.py
    --keep-trace`` logs the offset and the round's numbers."""
    import gzip
    import json
    trace = round_trace()
    trace["host"].append(["PjitFunction(_decode_fn)", 21 * MS, 0.3 * MS,
                          "main"])
    path = tmp_path / "kept.json.gz"
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)
    assert rt.main([str(path)]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out[0] == "[bench] host-device offset 1.0000 ms from 1 launches"
    assert json.loads(out[-1]) == rt.metrics(rt.reduce(trace))
