"""obs subsystem suite: telemetry, tracing, and profiler-driven tuning.

The contracts under test:

* **metrics == journal** — the drain-side counters emitted during a
  scripted flush equal (exactly, not approximately) the per-opcode row
  counts of the flush's :class:`JournalRecord`, and the queue-side
  counters equal the ticket's command count.
* **span nesting** — ``flush`` wraps ``drain`` (depth/parent recorded),
  ``ticket-wait`` records on ``wait()``, and capture/adopt regions keep
  the tree well-formed.
* **TunedProfile** — JSON round-trip, and the startup precedence chain
  *explicit kwarg > tuned profile > built-in default* observed by a real
  ``RowCloneEngine``.
* **autotuner smoke** — the tiny sweep matrix writes a profile the
  loader reads back, with the fused 1-launch invariant intact under
  every swept configuration.
* **bitwise parity** — a deterministic property-style command stream
  produces bit-identical pools and identical launch accounting with
  metrics+tracing ON vs OFF (the "always-on is free" contract).
* **adaptive ring** — sustained low admission pressure shrinks the
  staging ring (slots parked, counters/gauges emitted); demand regrows
  it before an admission would fail.
"""
import importlib.util
import pathlib

import jax
import numpy as np
import pytest

from repro.core import (BlockRef, FlushTicket, RowCloneEngine,
                        SubarrayAllocator, cmdqueue)
from repro.core.opcodes import OPCODE_NAMES
from repro.obs import metrics as obs
from repro.obs import trace
from repro.obs.autotune import (TunedProfile, load_profile, pick_winner,
                                save_profile)


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Each test sees an empty registry/span ring with span recording on,
    and leaves metrics on and recording as it found it."""
    obs.registry().reset()
    trace.reset_spans()
    prev = trace.set_tracing(True)
    yield
    obs.registry().reset()
    trace.reset_spans()
    obs.set_metrics_enabled(True)
    trace.set_tracing(prev)


def mk_engine(seed=0, nblk=32, snblk=8, **kw):
    alloc = SubarrayAllocator(nblk, 4, reserved_zero_per_slab=1)
    pools = {
        "k": jax.random.normal(jax.random.key(seed), (nblk, 4, 8)),
        "v": jax.random.normal(jax.random.key(seed + 1), (nblk, 4, 8)),
        "k_stage": jax.random.normal(jax.random.key(seed + 2), (snblk, 4, 8)),
        "v_stage": jax.random.normal(jax.random.key(seed + 3), (snblk, 4, 8)),
    }
    return RowCloneEngine(pools, alloc, max_requests=64,
                          staging={"k_stage": "k", "v_stage": "v"}, **kw)


# ---------------------------------------------------------------------------
# metrics == journal (exact equality)
# ---------------------------------------------------------------------------

def test_flush_metrics_match_journal_exactly():
    """Every drain counter of a scripted flush equals the journaled
    record: per-opcode row counts, spacer rows, launches — and the
    queue-side enqueue counters equal the ticket's command count."""
    eng = mk_engine()
    eng.alloc.mark_written([1, 2, 3])
    s = eng.stream("scripted")
    s.memcopy([(1, 5), (2, 6)])
    s.materialize_zeros([9, 10])
    s.memcopy_cross([(BlockRef("k_stage", 2), BlockRef("k", 11))])
    t = s.flush()
    assert isinstance(t, FlushTicket) and t.commands == 5

    rec = eng.journal.records[-1]
    assert rec.stream == "scripted"
    want: dict = {}
    spacers = 0
    for op, _src, _dst in rec.rows:
        if op < 0:
            spacers += 1
        else:
            name = OPCODE_NAMES[int(op)]
            want[name] = want.get(name, 0) + 1

    reg = obs.registry()
    got = {dict(labels)["opcode"]: int(v)
           for labels, v in reg.series("drain.rows").items()
           if dict(labels)["stream"] == "scripted"}
    assert got == want                                  # EXACT equality
    assert int(reg.get("drain.spacer_rows", stream="scripted")) == spacers
    assert int(reg.get("drain.launches", stream="scripted")) \
        == rec.launches == t.launches == 1
    enqueued = sum(v for labels, v in reg.series("queue.enqueued").items()
                   if dict(labels)["stream"] == "scripted")
    assert int(enqueued) == t.commands
    # histograms observed once for the single flush
    assert len(reg.hist("drain.flush_us", stream="scripted")) == 1
    assert reg.hist("drain.table_len", stream="scripted") \
        == [float(t.timing.table_len)]


def test_ticket_timing_field():
    """FlushTicket.timing carries the drain quad; empty flushes have
    None (nothing drained, nothing to time)."""
    eng = mk_engine(seed=2)
    eng.alloc.mark_written([4])
    s = eng.stream("timed")
    s.memcopy([(4, 9)])
    t = s.flush()
    assert t.timing is not None
    assert t.timing.launches == t.launches == 1
    assert t.timing.drain_us > 0.0
    assert t.timing.queue_residency_us >= 0.0
    assert t.timing.table_len >= 1
    t2 = s.flush()
    assert t2.timing is None            # empty flush: no drain happened


# ---------------------------------------------------------------------------
# span nesting
# ---------------------------------------------------------------------------

def test_span_nesting_flush_drain_wait():
    """flush() opens a "flush" span with the "drain" span nested inside
    (depth 1, parent = the flush record); wait() records "ticket-wait"."""
    eng = mk_engine(seed=5)
    eng.alloc.mark_written([2])
    s = eng.stream("spanned")
    with s.capture():                   # capture region: enqueue only
        eng.memcopy([(2, 7)])
    assert trace.spans("drain") == []   # capture alone drains nothing
    s.flush().wait()

    recs = trace.spans()
    flush_idx = [i for i, r in enumerate(recs) if r.name == "flush"]
    drains = [r for r in recs if r.name == "drain"]
    waits = [r for r in recs if r.name == "ticket-wait"]
    assert len(flush_idx) == 1 and len(drains) == 1 and len(waits) == 1
    f = recs[flush_idx[0]]
    d = drains[0]
    assert f.depth == 0 and f.parent == -1
    assert d.depth == 1 and d.parent == flush_idx[0]
    assert waits[0].depth == 0
    assert d.end >= d.start and f.end >= d.end >= f.start
    assert dict(f.labels)["stream"] == "spanned"
    tree = trace.span_tree()
    flush_node = next(n for n in tree if n["name"] == "flush")
    assert [c["name"] for c in flush_node["children"]] == ["drain"]


def test_set_tracing_off_records_nothing():
    """Tracing off: no records, engine behavior unchanged."""
    prev = trace.set_tracing(False)
    try:
        eng = mk_engine(seed=6)
        eng.alloc.mark_written([3])
        s = eng.stream("silent")
        s.memcopy([(3, 8)])
        t = s.flush()
        assert t.launches == 1
        assert trace.spans() == []
    finally:
        trace.set_tracing(prev)


def test_span_with_recording_off_leaves_no_record():
    """Recording off: a span is the profiler annotation alone — the ring
    stays empty and no sequence number is spent."""
    trace.set_tracing(False)
    with trace.span("engine.argmax"):
        with trace.span("engine.prefill", rid=3):
            pass
    assert trace.spans() == []
    trace.set_tracing(True)
    with trace.span("after"):
        pass
    assert [(r.name, r.seq) for r in trace.spans()] == [("after", 0)]


def test_ring_drop_keeps_parent_links_and_depth():
    """Past MAX_SPANS records the oldest drop; every survivor's parent
    still names its enclosing span by sequence number, and its depth is
    unchanged (a parent that dropped out makes a root in the tree)."""
    n_rounds = trace.MAX_SPANS // 3 + 10
    for i in range(n_rounds):
        with trace.span("sched.step", round=i):
            with trace.span("engine.decode_round"):
                with trace.span("engine.logits_fetch"):
                    pass
    recs = trace.spans()
    assert len(recs) == trace.MAX_SPANS
    assert recs[-1].seq == 3 * n_rounds - 1
    by_seq = {r.seq: r for r in recs}
    want = {"sched.step": (0, None), "engine.decode_round": (1, "sched.step"),
            "engine.logits_fetch": (2, "engine.decode_round")}
    for r in recs:
        depth, parent = want[r.name]
        assert r.depth == depth
        if parent is None:
            assert r.parent == -1
        elif r.parent in by_seq:
            assert by_seq[r.parent].name == parent
            assert r.parent == r.seq - 1
        else:
            assert r.parent < recs[0].seq     # dropped with the oldest
    last = trace.span_tree()[-1]
    assert last["labels"] == {"round": str(n_rounds - 1)}
    assert [c["name"] for c in last["children"]] == ["engine.decode_round"]


# ---------------------------------------------------------------------------
# TunedProfile round-trip + startup precedence
# ---------------------------------------------------------------------------

def test_profile_roundtrip_and_engine_precedence(tmp_path, monkeypatch):
    """kwarg > profile > default, observed through RowCloneEngine:
    a saved profile's overlap=False applies when the kwarg is omitted,
    an explicit kwarg wins, and no profile means the built-in default."""
    monkeypatch.delenv("REPRO_NO_TUNED", raising=False)
    monkeypatch.setenv("REPRO_TUNED_DIR", str(tmp_path))
    prof = TunedProfile(backend="cpu", buckets=(4, 16, 64, 256),
                        overlap=False, max_delta_signatures=4,
                        ring_capacity=3, us_per_flush=10.0,
                        baseline_us_per_flush=20.0,
                        swept={"flush": {"rows": []}})
    path = save_profile(prof)
    assert path == tmp_path / "cpu.json"
    assert load_profile() == prof                       # JSON round-trip

    eng = mk_engine()                   # no kwarg: profile wins
    assert eng.overlap is False and eng.profile == prof
    eng_kw = mk_engine(overlap=True)    # explicit kwarg beats profile
    assert eng_kw.overlap is True

    monkeypatch.setenv("REPRO_NO_TUNED", "1")
    assert load_profile() is None       # opt-out: no profile at all
    eng_def = mk_engine()
    assert eng_def.overlap is True and eng_def.profile is None


def test_profile_malformed_file_degrades_to_none(tmp_path, monkeypatch):
    monkeypatch.delenv("REPRO_NO_TUNED", raising=False)
    monkeypatch.setenv("REPRO_TUNED_DIR", str(tmp_path))
    (tmp_path / "cpu.json").write_text("{not json")
    assert load_profile() is None


def test_pick_winner_margin_rule():
    """A candidate unseats the default only past the 3% margin; the
    default's absence is an error (the sweep must measure it)."""
    rows = [{"cfg": {"x": 0}, "us_per_flush": 100.0},
            {"cfg": {"x": 1}, "us_per_flush": 98.0}]
    assert pick_winner(rows, {"x": 0})["cfg"] == {"x": 0}   # 2% < margin
    rows[1]["us_per_flush"] = 90.0
    assert pick_winner(rows, {"x": 0})["cfg"] == {"x": 1}   # 10% > margin
    with pytest.raises(ValueError):
        pick_winner(rows, {"x": 99})
    with pytest.raises(ValueError):
        pick_winner([], {"x": 0})


# ---------------------------------------------------------------------------
# autotuner smoke (tiny matrix)
# ---------------------------------------------------------------------------

def _load_bench_autotune():
    path = (pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
            / "bench_autotune.py")
    spec = importlib.util.spec_from_file_location("_test_bench_autotune",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.slow
def test_autotune_smoke_writes_loadable_profile(tmp_path, monkeypatch):
    """The quick sweep writes a per-backend profile that load_profile
    reads back, with baseline measured and 1.0 launches/flush under
    every swept configuration."""
    monkeypatch.delenv("REPRO_NO_TUNED", raising=False)
    ba = _load_bench_autotune()
    prof = ba.tune(out_dir=str(tmp_path), quick=True, skip_ring=True,
                   skip_mesh=True)
    assert (tmp_path / f"{prof.backend}.json").is_file()
    loaded = load_profile(directory=str(tmp_path))
    assert loaded == prof
    assert prof.baseline_us_per_flush > 0.0
    assert prof.us_per_flush <= prof.baseline_us_per_flush
    for row in prof.swept["flush"]["rows"]:
        assert row["launches_per_flush"] == 1.0
    # the sweep restored the process-wide default buckets
    assert cmdqueue.get_buckets() == cmdqueue.DEFAULT_BUCKETS


# ---------------------------------------------------------------------------
# metrics-on vs metrics-off: bitwise parity
# ---------------------------------------------------------------------------

def _scripted_rounds(rng, nblk, rounds=4):
    """A deterministic mixed script: per round, a few copies from
    already-written blocks, some zero inits, one cross-pool promotion."""
    script = []
    written = [1, 2, 3]
    for _ in range(rounds):
        srcs = rng.choice(written, size=2, replace=False).tolist()
        dsts = rng.choice(np.arange(nblk // 2, nblk - 1), size=2,
                         replace=False).tolist()
        zeros = rng.choice(np.arange(4, nblk // 2), size=2,
                           replace=False).tolist()
        stage = int(rng.integers(0, 4))
        promote = int(rng.integers(nblk // 2, nblk - 1))
        script.append((list(zip(srcs, dsts)), zeros, stage, promote))
        written = sorted(set(written) | set(dsts))
    return script


def test_metrics_on_off_pools_bitwise_identical():
    """The property-stream contract: the same command script with
    metrics+tracing ON vs OFF yields bit-identical pool bytes and
    identical launch accounting — observability never touches device
    state."""
    script = _scripted_rounds(np.random.default_rng(11), nblk=32)

    def run(flag):
        prev_m = obs.set_metrics_enabled(flag)
        prev_t = trace.set_tracing(flag)
        try:
            eng = mk_engine(seed=9)
            eng.alloc.mark_written([1, 2, 3])
            s = eng.stream("prop")
            launches = []
            for pairs, zeros, stage, promote in script:
                s.memcopy(pairs)
                s.materialize_zeros(zeros)
                s.memcopy_cross([(BlockRef("k_stage", stage),
                                  BlockRef("k", promote))])
                launches.append(s.flush().launches)
            jax.block_until_ready(list(eng.pools.values()))
            return {n: np.asarray(p).tobytes()
                    for n, p in eng.pools.items()}, launches
        finally:
            obs.set_metrics_enabled(prev_m)
            trace.set_tracing(prev_t)

    pools_on, launches_on = run(True)
    pools_off, launches_off = run(False)
    assert launches_on == launches_off
    assert set(pools_on) == set(pools_off)
    for name in pools_on:
        assert pools_on[name] == pools_off[name], \
            f"pool {name!r} bytes diverged metrics-on vs metrics-off"
    # and OFF really suppressed emission
    obs.registry().reset()
    prev = obs.set_metrics_enabled(False)
    try:
        obs.inc("drain.rows", 3, stream="x", opcode="fpm_copy")
    finally:
        obs.set_metrics_enabled(prev)
    assert obs.registry().series("drain.rows") == {}


# ---------------------------------------------------------------------------
# adaptive staging ring
# ---------------------------------------------------------------------------

def _mk_serving(**kw):
    from repro.configs import get_config
    from repro.launch.serve import ServingEngine
    from repro.models import build_model, split_params
    cfg = get_config("llama3.2-3b").reduced()
    model = build_model(cfg)
    params, _ = split_params(model.init_params(jax.random.key(0)))
    return cfg, ServingEngine(cfg, params, max_seqs=8,
                              max_blocks_per_seq=16, max_admit_pages=8,
                              **kw)


@pytest.mark.slow
def test_adaptive_ring_shrinks_then_regrows_on_demand():
    """Sustained low admission pressure parks staging slots (shrink);
    an admission that needs more slots than the clamped ring re-opens it
    before staging, so no admission ever fails to the clamp."""
    from repro.launch.serve import ServingEngine
    cfg, eng = _mk_serving(adaptive_ring=True)
    rng = np.random.default_rng(0)
    prompt = rng.integers(2, cfg.vocab_size, size=24).astype(np.int32)
    eng.add_request(prompt)
    # idle decode rounds: admitted-page pressure stays at/near zero, so
    # two RING_WINDOW cycles are enough to clamp the ring down
    for _ in range(2 * ServingEngine.RING_WINDOW + 1):
        eng.decode_round()
    assert eng.ring_shrinks >= 1
    limit = eng.engine.stage_limit
    assert limit is not None and limit < eng.engine.stage_capacity
    assert len(eng.engine._stage_parked) > 0
    reg = obs.registry()
    assert reg.get("serve.ring_shrinks") == eng.ring_shrinks
    assert reg.gauge_value("serve.ring_limit") == float(limit)
    # free + parked + in-flight always accounts for every slot
    assert len(eng.engine._stage_free) + len(eng.engine._stage_parked) \
        == eng.engine.stage_capacity

    # demand: a 2-page prompt (page_size=64 tokens) against the clamped
    # ring must regrow before staging
    big = rng.integers(2, cfg.vocab_size, size=100).astype(np.int32)
    sid = eng.add_request(big)
    assert eng.ring_regrows >= 1
    assert eng.engine.stage_limit is None       # fully re-opened
    assert reg.get("serve.ring_regrows") == eng.ring_regrows
    eng.decode_round()                          # and serving still works
    assert len(eng.tokens[sid]) >= 1


@pytest.mark.slow
def test_adaptive_ring_off_never_clamps():
    cfg, eng = _mk_serving(adaptive_ring=False)
    rng = np.random.default_rng(1)
    eng.add_request(rng.integers(2, cfg.vocab_size, size=24)
                    .astype(np.int32))
    from repro.launch.serve import ServingEngine
    for _ in range(2 * ServingEngine.RING_WINDOW + 1):
        eng.decode_round()
    assert eng.ring_shrinks == 0
    assert eng.engine.stage_limit is None
    assert eng.engine._stage_parked == []
