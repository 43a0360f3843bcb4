"""Traffic-layer suite: RequestScheduler rounds + sequence lifecycle.

Three layers, mirroring the serve_traffic benchmark gate:

* lifecycle regression — the free-before-flush bug: a sequence freed
  while its stage→KV promotion is still queued must RETIRE the promotion
  (rows leave the queues, staging slots recycle) instead of letting the
  stale copy land in blocks the allocator has re-issued.  The corruption
  leg re-allocates the freed blocks, writes a marker out of band, and
  asserts the marker survives the next flush — on the pre-fix engine the
  stale promotion clobbers it silently;
* scheduler semantics — continuous batching holds 1.0 bulk-movement
  launches per round under admission churn; priority preemption demotes
  a strictly-lower-priority victim, admits the waiter the NEXT round,
  resumes the victim later, and the preempted request's greedy tokens
  match a never-preempted run bitwise (the spill slots hold the real KV
  pages);
* mesh (subprocess, 8 host devices): the preempt→demote→resume parity
  leg under a (2, 4) mesh — demotion cross-pool copies ride the same
  collective launches as everything else.
"""
import jax
import numpy as np
import pytest

from _meshproc import run_device_subprocess
from repro.kernels import fused_dispatch as fd
from repro.kernels.fused_dispatch import OP_CROSS_POOL_COPY

PARITY_TOKENS = 8


def fd_hook():
    class _Hook:
        def __enter__(self):
            self.events = []
            self._fn = lambda n, p, m: self.events.append((n, p, m))
            fd.add_launch_hook(self._fn)
            return self.events

        def __exit__(self, *exc):
            fd.remove_launch_hook(self._fn)
    return _Hook()


@pytest.fixture(scope="module")
def served():
    from repro.configs import get_config
    from repro.models import build_model, split_params
    cfg = get_config("llama3.2-3b").reduced()
    model = build_model(cfg)
    params, _ = split_params(model.init_params(jax.random.key(0)))
    return cfg, params


# ---------------------------------------------------------------------------
# lifecycle: free before the round's flush
# ---------------------------------------------------------------------------

def test_free_before_flush_retires_promotion(served):
    """REGRESSION (fails on the pre-fix engine): freeing a sequence whose
    admission promotion has not flushed must retire the queued rows and
    recycle the staging slots — and the freed blocks, once re-issued,
    must never receive the dead sequence's staged bytes."""
    from repro.launch.serve import ServingEngine
    cfg, params = served
    eng = ServingEngine(cfg, params, max_seqs=8, max_blocks_per_seq=16,
                        max_admit_pages=4, double_buffer=True)
    prng = np.random.default_rng(11)
    prompt = prng.integers(2, cfg.vocab_size, size=16).astype(np.int32)
    sid = eng.add_request(prompt)
    blocks = list(eng.cache.blocks_of(sid))
    assert any(op == OP_CROSS_POOL_COPY
               for op, _, _ in eng.stream.pending)   # promotion is queued
    eng.free(sid)
    # the queued promotion must be gone, not waiting to fire later
    assert not any(op == OP_CROSS_POOL_COPY
                   for op, _, _ in eng.stream.pending), \
        "stale stage->KV promotion left queued after free()"
    # its staging slots are back in the ring (pre-fix: leaked in flight)
    # the adaptive ring may have parked free slots above its clamp —
    # nothing leaked as long as free + parked covers the whole ring
    assert len(eng.engine._stage_free) + len(eng.engine._stage_parked) \
        == eng.engine.stage_capacity
    # and recovery bookkeeping no longer names the dead sequence
    assert sid not in eng._staged_sids

    # corruption leg: the allocator re-issues the freed blocks to a NEW
    # sequence whose bytes arrive out of band (exactly how decode writes
    # pool pages, invisible to the queues' hazard tracking).  A stale
    # promotion would overwrite them at the next flush.
    sid2 = eng.cache.new_sequence(
        prompt_len=len(prompt),
        prefer_slab=eng.engine.alloc.slab_of(blocks[0]))
    blocks2 = list(eng.cache.blocks_of(sid2))
    assert set(blocks2) & set(blocks), "allocator did not re-issue blocks"
    eng.engine.alloc.mark_written(blocks2)
    marker = 3.25
    idx = np.asarray(blocks2)
    for n in ("k", "v"):
        eng.engine.pools[n] = eng.engine.pools[n].at[:, idx].set(marker)
    eng.stream.flush()
    for n in ("k", "v"):
        got = np.asarray(eng.engine.pools[n][:, idx])
        assert np.all(got == marker), \
            f"{n}: stale promotion corrupted re-issued blocks"


@pytest.mark.slow
def test_free_drops_extra_host_state():
    """Non-dense host state (conv/ssm) keyed by sid must die with the
    sequence: under churn the per-request entries previously accumulated
    forever.  Hybrid prefill populates ``_extras``; free() must pop it
    (decode for this family goes through model.decode_step directly, so
    the test exercises admission + free only)."""
    from repro.configs import get_config
    from repro.launch.serve import ServingEngine
    from repro.models import build_model, split_params
    cfg = get_config("zamba2-2.7b").reduced()
    model = build_model(cfg)
    params, _ = split_params(model.init_params(jax.random.key(0)))
    eng = ServingEngine(cfg, params, max_seqs=4, max_blocks_per_seq=8)
    prng = np.random.default_rng(0)
    sids = [eng.add_request(prng.integers(2, cfg.vocab_size, size=8)
                            .astype(np.int32)) for _ in range(3)]
    assert all(s in eng._extras for s in sids)
    for s in sids:
        eng.free(s)
    assert eng._extras == {}
    assert eng.cache.seqs == {}


# ---------------------------------------------------------------------------
# scheduler: continuous batching, QoS lanes, preemption
# ---------------------------------------------------------------------------

def _sched_engine(cfg, params, **kw):
    from repro.launch.serve import ServingEngine
    base = dict(max_seqs=4, max_blocks_per_seq=8, num_slabs=2,
                max_admit_pages=8, double_buffer=True, spill_pages=8)
    base.update(kw)
    return ServingEngine(cfg, params, **base)


def test_continuous_batching_single_launch_and_reclaim(served):
    """Staggered admissions/retirements across two lanes: every round's
    bulk movement is at most ONE fused launch, and a fully drained
    scheduler returns every block, batch slot, staging slot, and spill
    slot."""
    from repro.launch.scheduler import RequestScheduler, TenantSpec
    cfg, params = served
    eng = _sched_engine(cfg, params)
    free0 = eng.engine.alloc.total_free()
    sched = RequestScheduler(eng, [TenantSpec("gold", 1),
                                   TenantSpec("free", 0)])
    prng = np.random.default_rng(3)
    plan = {0: [("free", 9), ("free", 16)], 1: [("gold", 9)],
            3: [("free", 24)]}
    r = 0
    while not sched.idle or r < 5:
        for tenant, plen in plan.get(r, []):
            sched.submit(tenant, prng.integers(2, cfg.vocab_size, size=plen)
                         .astype(np.int32), max_new_tokens=4)
        with fd_hook() as ev:
            rep = sched.step()
        assert rep.launches <= 1, (r, rep)
        assert all(m == "fused" for _, _, m in ev), ev
        r += 1
        assert r < 60, "scheduler failed to drain"
    assert all(q.state == "done" for q in sched.requests.values())
    assert all(len(q.tokens_out) == q.max_new_tokens
               for q in sched.requests.values())
    # everything reclaimed: sequences, pool blocks, staging + spill slots
    assert eng.cache.seqs == {}
    assert eng.engine.alloc.total_free() == free0
    # the adaptive ring may have parked free slots above its clamp —
    # nothing leaked as long as free + parked covers the whole ring
    assert len(eng.engine._stage_free) + len(eng.engine._stage_parked) \
        == eng.engine.stage_capacity
    assert eng.engine.spill_slots_free == eng.engine.spill_capacity


@pytest.mark.slow
def test_round_spans_cover_the_step(served):
    """One round that admits a request and decodes, recorded: the
    ``sched.step`` root holds the scheduler's stages in order, the
    admission's ``engine.prefill`` carries its request id, and the
    decode's stages (logits fetch, dispatch) sit under
    ``engine.decode_round``."""
    from repro.launch.scheduler import RequestScheduler, TenantSpec
    from repro.obs import trace
    cfg, params = served
    eng = _sched_engine(cfg, params)
    sched = RequestScheduler(eng, [TenantSpec("gold", 1)])
    rid = sched.submit("gold", np.arange(2, 11, dtype=np.int32),
                       max_new_tokens=2)
    prev = trace.set_tracing(True)
    trace.reset_spans()
    try:
        sched.step()
        tree = trace.span_tree()
    finally:
        trace.set_tracing(prev)
        trace.reset_spans()
    steps = [n for n in tree if n["name"] == "sched.step"]
    assert len(steps) == 1 and steps[0]["labels"] == {"round": "0"}
    kids = steps[0]["children"]
    assert [c["name"] for c in kids] == [
        "sched.retire", "sched.admit", "sched.preempt", "sched.adopt",
        "engine.decode_round", "sched.report"]
    admit = kids[1]
    assert [c["name"] for c in admit["children"]] == ["engine.prefill"]
    prefill = admit["children"][0]
    assert prefill["labels"] == {"rid": str(rid)}
    assert [c["name"] for c in prefill["children"]] == [
        "engine.prefill_dispatch", "engine.prefill_logits"]
    decode = [c["name"] for c in kids[4]["children"]]
    assert decode == ["engine.argmax", "engine.cow_append", "flush",
                      "engine.table_upload", "engine.decode_dispatch",
                      "engine.logits_fetch", "engine.post_decode"]
    flush = kids[4]["children"][2]
    assert [c["name"] for c in flush["children"]] == ["drain"]


def test_preempt_demote_resume_bitwise_parity(served):
    """A gold arrival on a full 2-slot engine demotes a free-tenant
    victim (OP_CROSS_POOL_COPY into the spill slots), admits the NEXT
    round, and the victim resumes later — with every request's greedy
    tokens bitwise-identical to a roomy engine that never preempts, at
    <= 1.0 launches/round throughout."""
    from repro.launch.scheduler import RequestScheduler, TenantSpec
    cfg, params = served
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, cfg.vocab_size, size=16).astype(np.int32)
               for _ in range(3)]
    tenants = [TenantSpec("gold", 2), TenantSpec("free", 0)]

    def drive(eng):
        sched = RequestScheduler(eng, tenants)
        rids = [sched.submit("free", prompts[0],
                             max_new_tokens=PARITY_TOKENS),
                sched.submit("free", prompts[1],
                             max_new_tokens=PARITY_TOKENS)]
        sched.step()
        sched.step()
        rids.append(sched.submit("gold", prompts[2],
                                 max_new_tokens=PARITY_TOKENS))
        sched.drain(max_rounds=120)
        return sched, rids

    roomy, roomy_rids = drive(_sched_engine(cfg, params, max_seqs=8,
                                            num_slabs=4, spill_pages=0))
    tight, tight_rids = drive(_sched_engine(cfg, params, max_seqs=2))

    assert sum(q.preemptions for q in roomy.requests.values()) == 0
    assert sum(q.preemptions for q in tight.requests.values()) > 0
    assert max(r.launches for r in tight.reports) <= 1
    # the gold waiter admits exactly one round after the demotion (the
    # victim's blocks come back at the demotion round's flush)
    gold_rid = tight_rids[2]
    demote_round = next(r.round_index for r in tight.reports if r.preempted)
    admit_round = next(r.round_index for r in tight.reports
                       if gold_rid in r.admitted)
    assert admit_round == demote_round + 1
    # the victim resumed and everyone finished
    assert any(r.resumed for r in tight.reports)
    assert all(q.state == "done" for q in tight.requests.values())
    # bitwise parity: preemption round-trips the real KV bytes
    assert [tight.requests[r].tokens_out for r in tight_rids] == \
        [roomy.requests[r].tokens_out for r in roomy_rids]


def test_cancel_in_every_state(served):
    """cancel() unwinds a request whether it is queued (never admitted),
    running (frees the live sequence — mid-round promotions retire), or
    preempted (releases the spill parking slots)."""
    from repro.launch.scheduler import RequestScheduler, TenantSpec
    cfg, params = served
    eng = _sched_engine(cfg, params, max_seqs=2)
    sched = RequestScheduler(eng, [TenantSpec("gold", 1),
                                   TenantSpec("free", 0)])
    prng = np.random.default_rng(7)
    mk = lambda n: prng.integers(2, cfg.vocab_size, size=n).astype(np.int32)
    r_free = [sched.submit("free", mk(9), max_new_tokens=32),
              sched.submit("free", mk(9), max_new_tokens=32)]
    sched.step()
    sched.step()
    r_gold = sched.submit("gold", mk(9), max_new_tokens=4)
    sched.step()                        # demotes one free victim
    parked = next(r for r in r_free
                  if sched.requests[r].state == "preempted")
    running = next(r for r in r_free if r != parked)
    sched.cancel(parked)                # spill parking released
    assert sched.requests[parked].state == "cancelled"
    assert eng.engine.spill_slots_free == eng.engine.spill_capacity
    sched.cancel(running)               # live sequence freed
    r_q = sched.submit("free", mk(9), max_new_tokens=4)
    sched.cancel(r_q)                   # still queued: just dequeued
    assert sched.requests[r_q].state == "cancelled"
    sched.drain(max_rounds=60)          # gold still completes
    assert sched.requests[r_gold].state == "done"
    assert len(sched.requests[r_gold].tokens_out) == 4
    assert eng.cache.seqs == {}


def test_demote_while_staged_is_refused(served):
    """A sequence admitted THIS round (promotion still queued) cannot be
    demoted — the parked bytes would race the promotion.  The scheduler
    defers such victims a round; the engine enforces it."""
    from repro.launch.serve import ServingEngine
    cfg, params = served
    eng = _sched_engine(cfg, params)
    prng = np.random.default_rng(1)
    sid = eng.add_request(prng.integers(2, cfg.vocab_size, size=9)
                          .astype(np.int32))
    with pytest.raises(RuntimeError, match="not drained"):
        eng.demote(sid)
    eng.decode_round()
    eng.demote(sid)                     # next round it is fair game
    assert sid in eng.demoted
    eng.free(sid)                       # parked free releases the slots
    assert eng.engine.spill_slots_free == eng.engine.spill_capacity


# ---------------------------------------------------------------------------
# mesh leg: preempt -> demote -> resume parity under a (2, 4) device mesh
# ---------------------------------------------------------------------------

MESH_SCHED_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import json
import jax
import numpy as np
from jax.sharding import Mesh

from repro.configs import get_config
from repro.launch.scheduler import RequestScheduler, TenantSpec
from repro.launch.serve import ServingEngine
from repro.models import build_model, split_params

mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("data", "model"))
cfg = get_config("llama3.2-3b").reduced()
model = build_model(cfg)
params, _ = split_params(model.init_params(jax.random.key(0)))
rng = np.random.default_rng(0)
prompts = [rng.integers(2, cfg.vocab_size, size=16).astype(np.int32)
           for _ in range(3)]
tenants = [TenantSpec("gold", 2), TenantSpec("free", 0)]

def drive(eng):
    sched = RequestScheduler(eng, tenants)
    rids = [sched.submit("free", prompts[0], max_new_tokens=8),
            sched.submit("free", prompts[1], max_new_tokens=8)]
    sched.step(); sched.step()
    rids.append(sched.submit("gold", prompts[2], max_new_tokens=8))
    sched.drain(max_rounds=120)
    return ([sched.requests[r].tokens_out for r in rids],
            sum(q.preemptions for q in sched.requests.values()),
            max(r.launches for r in sched.reports))

roomy = ServingEngine(cfg, params, mesh=mesh, max_seqs=8,
                      max_blocks_per_seq=8, num_slabs=4,
                      max_admit_pages=8, double_buffer=True)
ref_tokens, ref_pre, _ = drive(roomy)
tight = ServingEngine(cfg, params, mesh=mesh, max_seqs=2,
                      max_blocks_per_seq=8, num_slabs=2,
                      max_admit_pages=8, double_buffer=True, spill_pages=8)
tokens, pre, launches = drive(tight)
print("RESULTS:" + json.dumps({
    "tokens_match": tokens == ref_tokens,
    "preempted": int(pre),
    "ref_preempted": int(ref_pre),
    "max_launches": int(launches),
}))
"""


@pytest.mark.mesh
@pytest.mark.slow
def test_scheduler_preemption_parity_mesh(tmp_path):
    """8 host devices, (2, 4) mesh: demote/resume cross-pool copies ride
    the collective fused launches, and the preempted run's greedy tokens
    still match the roomy never-preempting twin bitwise."""
    res = run_device_subprocess(MESH_SCHED_CHILD, tmp_path=tmp_path)
    assert res["tokens_match"], res
    assert res["preempted"] > 0 and res["ref_preempted"] == 0, res
    assert res["max_launches"] <= 1, res
