"""Serving engine: continuous batched decode over a RowClone-managed pool.

The serving loop is the paper's application showcase:

* admission (``add_request``) — the prefill forward writes its KV pages
  directly into the engine's **staging pools** (inside the prefill jit —
  no separate staging dispatch), and the stage→KV-pool promotion enqueues
  ``OP_CROSS_POOL_COPY`` commands into the engine's command queue (this is
  the CPU→"process address space" copy that RowClone §3.2 accelerates,
  expressed as the GS-DRAM-style pool→pool transfer);
* ``fork`` — parallel sampling / beam search shares every prompt page by
  refcount (zero bytes), CoW-splitting lazily on the first divergent append;
* fresh pages are BuZ-lazy-zeroed (ZI metadata bit);
* ``dedup_admit=True`` — **dedup-on-admit**: every staged prompt page is
  fingerprinted with an XOR fold (:func:`page_fingerprint` — XOR composed
  from the engine's new in-memory bitwise opcode identities, ``x ^ y ==
  (x | y) & ~(x & y)``), and pages whose chained fingerprint matches a
  live registry entry collapse onto the donor's block: the dupe's
  promotion rows are skipped, its staging slots return to the ring, and
  the shared block rides the round's single fused launch exactly like a
  CoW fork share.  The first divergent append CoW-splits, and greedy
  tokens stay bitwise-identical to a dedup-off run;
* each decode round drains the engine's **serve CommandStream** ONCE —
  promotions + CoW splits + tail inits are captured onto the stream
  (``stream.capture()``) and ride one fused launch at ``stream.flush()``,
  whose :class:`~repro.core.stream.FlushTicket` is kept in
  ``last_ticket`` — then runs one jit'd ``model.decode_step`` over the
  shared pool with the cache's device tables.  Under a mesh the batch
  shards over (pod, data) whenever the cache can pin each sequence's
  blocks in its group's slabs (``batch_shard_count``); the flush is one
  collective launch either way.

Staging sizing is policy-derived: ``max_admit_pages=None`` sizes the ring
at ``admissions_per_round x max_blocks_per_seq`` (the most pages an
in-policy round can park); ``double_buffer=True`` doubles the slots into
a live + shadow half, so admission bursts past the ring's nominal
capacity land in the shadow half while the live half's promotions are
still queued (their slots carry pending READS — the command queues'
source-hazard tracking) and the round still drains as ONE launch.
``max_admit_pages=ServingEngine.FULL_TWIN`` keeps the seed's full-size
staging twins.

``fused_staging=False`` restores the seed's ``_stage_legacy`` path (one
ad-hoc gather/scatter dispatch per pool per admission, KV pools written
directly) for A/B benchmarking — ``benchmarks/bench_dispatch.py
serve_round`` and the staging parity suite drive both.

CLI:  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b \
          --requests 8 --steps 32 --fork 2 [--full]

Without ``--full`` the CLI serves the config's reduced smoke variant;
with it, the published widths in the config's dtype (bf16 for
llama3.2-3b: 6.4 GB of weights and 1.9 GB of KV pools, which fits one
16 GB TPU v5e).  Weights are random, drawn from ``--seed``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import os
import pathlib
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager, PoolCheckpoint
from repro.configs import RowCloneConfig, get_config
from repro.core import PagedCoWCache, RowCloneEngine, SubarrayAllocator
from repro.core.journal import RecoveryReport
from repro.kernels.fused_dispatch import notify_launch
from repro.launch.mesh import pool_shard_count
from repro.models import build_model, split_params
from repro.models.paged import batch_shard_count, make_serving_pools
from repro.obs import metrics as obs_metrics
from repro.obs.autotune import load_profile


@dataclasses.dataclass
class DemotedSeq:
    """Host-side parking record for a preempted sequence.

    :meth:`ServingEngine.demote` moves a victim's KV blocks into spill
    slots (``OP_CROSS_POOL_COPY`` — the reverse of admission promotion)
    and keeps everything needed to resume bitwise-identically here:
    length, the spill slots holding the bytes, slab affinity, the last
    logits (next-token source), the token history, and any extra host
    state (conv/ssm/cross-attention).  The KV pool blocks themselves are
    returned to the allocator after the round's flush."""

    length: int                  #: sequence length at demotion time
    slots: List[int]             #: spill slots parking the KV bytes
    slab_home: int               #: preferred slab for re-allocation
    logits: np.ndarray           #: last logits (greedy argmax source)
    tokens: List[int]            #: token history (prompt + generated)
    extras: Optional[dict]       #: non-dense host state, if any


#: 64-bit fold constants (splitmix64 / FNV mixes) for the page fingerprint
_FP_MASK = (1 << 64) - 1
_FP_WORD = 0x9E3779B97F4A7C15
_FP_POS = 0xC2B2AE3D27D4EB4F
_FP_CHAIN = 0x100000001B3


def xor_fold(acc: int, word: int) -> int:
    """One XOR-fold step over 64-bit words, composed EXACTLY from the
    engine's in-memory bitwise opcode identities: ``x ^ y == (x | y) &
    ~(x & y)`` — an ``OP_OR``, an ``OP_AND``, an ``OP_NOT``, and a final
    ``OP_AND``.  The host-side software analogue of folding a block
    fingerprint in DRAM with the Ambit triple-row ops the fused dispatch
    now executes (``memand``/``memor``/``memnot``)."""
    both = acc & word           # OP_AND
    either = acc | word         # OP_OR
    return (either & (~both & _FP_MASK)) & _FP_MASK   # OP_AND of OP_NOT


def page_fingerprint(chain: int, tokens) -> int:
    """Chained fingerprint of one prompt page: position-salted token
    words folded with :func:`xor_fold`, mixed into the previous page's
    fingerprint (``chain``) so equal keys mean equal page *prefixes*, not
    just equal pages.  Dedup-on-admit keys its prefix registry with
    these (and verifies the raw tokens on every hit, so a fold collision
    can never corrupt a sequence)."""
    fp = chain & _FP_MASK
    for i, t in enumerate(tokens):
        word = ((int(t) + 1) * _FP_WORD + (i + 1) * _FP_POS) & _FP_MASK
        fp = xor_fold((fp * _FP_CHAIN) & _FP_MASK, word)
    # fold the page's token count so a short tail page can never alias a
    # full page that starts with the same tokens
    return xor_fold(fp, (len(tokens) * _FP_POS) & _FP_MASK)


class ServingEngine:
    """Continuous-batching serving facade over RowCloneEngine +
    PagedCoWCache: admission (prefill + staged promotion), CoW fork,
    preemption by demotion (:meth:`demote`/:meth:`resume`), dedup-on-admit
    (``dedup_admit=True`` — identical prompt prefixes across tenants
    collapse onto shared CoW blocks at admission), and greedy decode
    rounds whose bulk movement drains as one fused launch."""

    #: ``max_admit_pages`` sentinel: keep full-size staging twins (every
    #: KV block has a staging slot) instead of a recycled ring
    FULL_TWIN = 0

    #: adaptive-ring observation window: rounds of sustained low
    #: admission pressure before the staging ring shrinks
    RING_WINDOW = 4

    def __init__(self, cfg, params, mesh=None, max_seqs: int = 16,
                 max_blocks_per_seq: int = 64, num_slabs: int = 4,
                 rc: Optional[RowCloneConfig] = None, impl: str = "ref",
                 fused_staging: bool = True,
                 max_admit_pages: Optional[int] = None,
                 admissions_per_round: int = 1,
                 double_buffer: bool = False,
                 fault_plan=None, auto_recover: bool = False,
                 ckpt_pages: int = 0, ckpt_dir: Optional[str] = None,
                 ckpt_window: Optional[int] = None,
                 spill_pages: int = 0, dedup_admit: bool = False,
                 adaptive_ring: bool = True):
        """``max_admit_pages`` sizes the staging pools as a RING of that
        many slots instead of a full-size twin of the KV pools — slots
        recycle at every round's flush, so the ring only needs to hold
        the pages admitted between two flushes.  ``None`` (default)
        DERIVES the size from the admission policy:
        ``admissions_per_round x max_blocks_per_seq`` (the most pages an
        in-policy round can park); :data:`FULL_TWIN` (0) keeps the seed's
        full twin.  A ring of a few blocks cuts the engine's resident
        pool bytes by ~2x at unchanged round latency and bitwise-identical
        decode (BENCH_dispatch.json serve_round).

        ``double_buffer=True`` doubles the ring into live + shadow
        halves: admissions bursting past the nominal ring capacity park
        in the shadow half while the live half's promotions are still
        queued on the serve stream (pending source reads guard those
        slots), keeping burst rounds at 1.0 bulk-movement launches
        instead of forcing an early drain.

        Under a mesh a ring that does not divide the pool shard count is
        REPLICATED (``PoolSpec.sharding == ()`` — held whole on every
        device) rather than rounded up; sharded rings partition like
        their KV twins.

        Fault tolerance: ``ckpt_pages > 0`` adds spill pools of that many
        blocks and a background :class:`PoolCheckpoint` driven one window
        per decode round (``ckpt_dir`` names the checkpoint directory);
        ``fault_plan`` installs a
        :class:`~repro.runtime.fault.FaultPlan`'s injections against this
        engine; ``auto_recover=True`` catches a failed round flush (or
        ckpt tick) and runs :meth:`recover` in place — the next round
        serves normally.  Admissions evicted by a recovery land in
        ``evicted_sids`` for the caller to re-admit.

        ``adaptive_ring=True`` (the default) lets the staging ring track
        admission pressure: after :data:`RING_WINDOW` consecutive rounds
        whose admitted pages peak at or below half the usable ring, the
        ring shrinks (``engine.set_stage_limit``) to twice that peak —
        free slots above the limit park, cutting the ring's working set;
        an admission that would not fit the clamped ring regrows it to
        full capacity BEFORE reserving slots, so admissions never fail
        or force an early flush because of the clamp.  The
        ``serve.ring_occupancy`` / ``serve.ring_limit`` gauges and the
        shrink/regrow counters ride the obs metrics registry.

        Dedup-on-admit: ``dedup_admit=True`` (fused staging only) keeps a
        prefix registry of chained page fingerprints
        (:func:`page_fingerprint`).  An admission whose prompt pages
        match live registry entries shares the donor blocks by refcount
        instead of promoting its own staged copies — the matched
        promotion rows never enqueue, the staging slots return to the
        ring immediately, and resident KV bytes (:meth:`kv_bytes_live`)
        grow by only the unmatched pages.  Registered pages pin one
        registry refcount so their bytes can never be recycled under a
        live entry; :meth:`free` of the registering sequence drops its
        entries.  Under sharded batches a donor block is only shared
        into a sequence pinned to the same batch group.

        Preemption: ``spill_pages > 0`` reserves that many EXTRA spill
        slots for :meth:`demote` / :meth:`resume` — the scheduler's
        preemption-by-demotion path.  The spill pools are shared with the
        checkpoint stream but partitioned by slot range: PoolCheckpoint
        windows keep slots ``[0, ckpt_pages)``, demotion owns
        ``[ckpt_pages, ckpt_pages + spill_pages)`` — the two never
        collide, and both ride the same ``OP_CROSS_POOL_COPY`` fused
        launches."""
        self.cfg = cfg
        self.rc = rc or RowCloneConfig()
        self.mesh = mesh
        self.impl = impl
        self.model = build_model(cfg, self.rc)
        self.params = params
        self.fused_staging = fused_staging
        self.double_buffer = double_buffer
        page = self.rc.page_size
        L = cfg.num_attn_layers
        nblk = max_seqs * max_blocks_per_seq
        # pool must tile both the allocator slabs and the mesh's device
        # shards — the sharded fused dispatch partitions by device shard
        shards = pool_shard_count(mesh)
        align = int(np.lcm(num_slabs, shards))
        nblk = -(-nblk // align) * align
        if max_admit_pages is None:
            # tuned-profile precedence: an autotuned ring size applies
            # only when the caller did not pass an explicit kwarg
            # (kwarg > profile > policy derivation)
            prof = load_profile()
            if prof is not None and prof.ring_capacity is not None:
                max_admit_pages = int(prof.ring_capacity)
        if max_admit_pages is None:
            # admission-policy derivation: the ring must hold one round's
            # worth of staged pages (kwarg stays as an explicit override)
            max_admit_pages = admissions_per_round * max_blocks_per_seq
        replicate_staging = False
        if max_admit_pages == self.FULL_TWIN:
            stage_nblk = nblk          # full twin (seed sizing)
            self.ring_capacity = nblk
        else:
            self.ring_capacity = int(max_admit_pages)
            stage_nblk = int(max_admit_pages) * (2 if double_buffer else 1)
            if stage_nblk % shards:
                replicate_staging = True   # whole ring on every device
        kv_dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        alloc = SubarrayAllocator(nblk, num_slabs,
                                  reserved_zero_per_slab=self.rc
                                  .zero_blocks_per_slab)
        # K/V pools + staging pools are ONE PoolGroup (models/paged.py):
        # per-pool block counts in the group's prefix-sum address space,
        # so the (possibly much smaller) staging ring rides the same
        # fused launch.  The engine sees the mesh: every decode round's
        # promotions + CoW splits + tail inits drain as ONE (collective)
        # launch at the round's flush boundary
        self.ckpt_pages = int(ckpt_pages)
        self.spill_pages = int(spill_pages)
        # one spill pool per primary (PoolCheckpoint keys spill pools by
        # their paired primary): checkpoint windows and demotion parking
        # SHARE it, partitioned by slot range
        total_spill = self.ckpt_pages + self.spill_pages
        replicate_ckpt = bool(total_spill % shards) if total_spill else False
        pools, group = make_serving_pools(
            L, nblk, page, cfg.num_kv_heads, cfg.head_dim, kv_dtype,
            staging=fused_staging, stage_nblk=stage_nblk,
            replicate_staging=replicate_staging,
            ckpt_nblk=total_spill, replicate_ckpt=replicate_ckpt)
        if mesh is not None:
            # honor each PoolSpec's sharding hint at placement time
            # (replicated rings stay whole per device; KV pools shard)
            from repro.launch.mesh import tree_shardings
            shardings = tree_shardings(
                mesh, pools, {n: group[n] for n in pools}, block_axis=1)
            pools = {n: jax.device_put(a, shardings[n])
                     for n, a in pools.items()}
        self.engine = RowCloneEngine(
            pools, alloc, mesh=mesh, enable_fpm=self.rc.enable_fpm,
            enable_psm=self.rc.enable_psm, enable_zi=self.rc.enable_zi,
            block_axis=1, group=group)
        # shard the decode batch over (pod, data) when the cache can pin
        # each sequence's blocks inside its batch group's slabs; otherwise
        # keep global share-mask columns (replicated batch — paged.py)
        dp = batch_shard_count(mesh, max_seqs)
        if dp > 1 and (num_slabs % dp or nblk % dp):
            dp = 1
        self.cache = PagedCoWCache(self.engine, page, max_blocks_per_seq,
                                   max_seqs, batch_groups=dp)
        self.last_logits: Dict[int, np.ndarray] = {}
        self.tokens: Dict[int, List[int]] = {}
        self._decode_jit = jax.jit(self._decode_fn, donate_argnums=(1, 2))
        # the staging pools ARE donated: a failure inside the donated call
        # kills buffers still holding earlier admissions' un-promoted
        # pages, and recover() handles exactly that — it resurrects the
        # staging ring and evicts the affected admissions (evicted_sids)
        # for re-admission.  Donation closes the seed-era extra copy the
        # un-donated scatter paid per admission.
        self._prefill_stage_jit = jax.jit(self._prefill_stage_fn,
                                          donate_argnums=(2, 3))
        self._prefill_jit = jax.jit(functools.partial(
            self.model.prefill, mesh=mesh, margin_tokens=0))
        # the round's bulk movement lives on a dedicated CommandStream:
        # admissions/forks CAPTURE their promotions and CoW work onto it,
        # and decode_round's stream.flush() drains everything as one
        # launch, returning the FlushTicket kept in ``last_ticket``
        self.stream = self.engine.stream("serve")
        self.last_ticket = None
        self.auto_recover = auto_recover
        self.fault_plan = fault_plan
        if fault_plan is not None:
            fault_plan.install(self.engine)
        #: admissions whose stage→KV promotions have not drained yet —
        #: recovery evicts exactly these when the staged bytes are lost
        self._staged_sids: List[int] = []
        #: per-admission stage→KV promotion pairs still queued — free()
        #: retires exactly these rows so a freed-before-flush sequence's
        #: promotion can never land in re-issued blocks
        self._pending_promotions: Dict[int, List[Tuple[int, int]]] = {}
        #: per-seq host state (conv/ssm/cross-attention) for non-dense
        #: families, keyed by sid — free()/demote() MUST drop the entry
        self._extras: Dict[int, dict] = {}
        #: sequences a recovery evicted; the caller re-admits their
        #: prompts (re-admission reproduces the KV bytes, so greedy
        #: tokens match the failure-free run)
        self.evicted_sids: List[int] = []
        #: preempted sequences parked in spill slots, keyed by sid —
        #: :meth:`resume` unparks (minting a NEW sid); :meth:`free`
        #: releases the parking without resuming
        self.demoted: Dict[int, DemotedSeq] = {}
        #: resumes whose spill→KV promotions have not drained yet —
        #: recovery evicts these the same way it evicts staged admissions
        self._resumed: List[Tuple[int, List[int]]] = []
        #: demoted blocks kept allocated until the round's flush drains
        #: the demote reads — freeing them early would let a same-round
        #: admission reuse the block and trip the cross-stream WAR guard
        #: (an extra launch), breaking the 1.0 launches/round contract
        self._free_after_flush: List[int] = []
        self._admission_ordinal = 0
        #: dedup-on-admit prefix registry: chained page fingerprint ->
        #: (donor block id, raw page tokens) — the token tuple is checked
        #: on every hit, so fingerprint collisions degrade to a miss
        self.dedup_admit = bool(dedup_admit) and fused_staging
        self._dedup_registry: Dict[int, Tuple[int, Tuple[int, ...]]] = {}
        #: registry keys registered per sid (free() drops them and
        #: releases the registry's own block refcount)
        self._dedup_keys: Dict[int, List[int]] = {}
        self.dedup_hits = 0           #: admissions that shared >= 1 page
        self.dedup_pages_shared = 0   #: prompt pages satisfied by sharing
        self.dedup_bytes_saved = 0    #: KV bytes those pages never took
        #: adaptive staging-ring controller (fused staging only): shrink
        #: under sustained low admission pressure, regrow on demand
        self.adaptive_ring = bool(adaptive_ring) and fused_staging
        self._ring_window: List[int] = []   #: admitted pages, last rounds
        self._round_admitted_pages = 0
        self.ring_shrinks = 0         #: times the controller clamped the ring
        self.ring_regrows = 0         #: times demand re-opened the full ring
        self.last_recovery: Optional[RecoveryReport] = None
        self.pool_ckpt: Optional[PoolCheckpoint] = None
        if self.ckpt_pages:
            if ckpt_dir is None:
                raise ValueError("ckpt_pages > 0 needs ckpt_dir")
            # cap the checkpoint window at ckpt_pages: with demotion the
            # spill pools are oversized, and windows must stay out of the
            # demotion slot range
            self.pool_ckpt = PoolCheckpoint(
                self.engine, CheckpointManager(ckpt_dir),
                window=(min(int(ckpt_window), self.ckpt_pages)
                        if ckpt_window is not None else self.ckpt_pages))
        if self.spill_pages:
            self.engine.enable_demotion(
                range(self.ckpt_pages, self.ckpt_pages + self.spill_pages))

    # ------------------------------------------------------------------
    def _prefill_batch(self, prompt: np.ndarray) -> Dict[str, jnp.ndarray]:
        S = int(prompt.shape[0])
        batch = {"tokens": jnp.asarray(prompt[None, :])}
        if self.cfg.family == "vlm":
            batch["patch_embeds"] = jnp.zeros(
                (1, self.cfg.vision_tokens, self.cfg.d_model), jnp.float32)
        if self.cfg.family == "encdec":
            batch["src_embeds"] = jnp.zeros(
                (1, max(S // self.cfg.src_frames_ratio, 1),
                 self.cfg.d_model), jnp.float32)
        return batch

    def _prefill_stage_fn(self, params, batch, k_stage, v_stage, stage_ids):
        """Prefill forward + scatter of the prompt's KV pages into the
        staging pools, ONE jit: the staged write costs no extra dispatch,
        and the only bulk movement left (staging→KV promotion) goes
        through the command queue."""
        logits, st = self.model.prefill(params, batch, self.mesh,
                                        margin_tokens=0)
        safe = jnp.where(stage_ids >= 0, stage_ids, k_stage.shape[1])
        k_stage = k_stage.at[:, safe].set(
            st["k_pools"].astype(k_stage.dtype), mode="drop")
        v_stage = v_stage.at[:, safe].set(
            st["v_pools"].astype(v_stage.dtype), mode="drop")
        extras = {k: st[k] for k in ("conv_state", "ssm_state",
                                     "cross_k", "cross_v") if k in st}
        return logits, k_stage, v_stage, extras

    def add_request(self, prompt: np.ndarray,
                    stream=None) -> int:
        """prompt: (S,) int32.  Prefill into the staging pools and enqueue
        the stage→KV promotion (fused path), or scatter straight into the
        KV pools (seed ``fused_staging=False`` path).

        ``stream`` routes the admission's bulk movement onto a caller
        stream instead of the engine's serve stream — the scheduler's
        per-tenant QoS lanes admit here and
        :meth:`~repro.core.stream.CommandStream.adopt` their rows into
        the round stream in priority order."""
        stream = self.stream if stream is None else stream
        S = int(prompt.shape[0])
        if self.fused_staging:
            # any block inits the admission needs (e.g. ZI disabled) ride
            # the serve stream with the round's other bulk movement
            with stream.capture():
                sid = self.cache.new_sequence(prompt_len=S)
        else:
            sid = self.cache.new_sequence(prompt_len=S)
        batch = self._prefill_batch(prompt)
        blocks = self.cache.blocks_of(sid)
        if self.fused_staging:
            ordinal = self._admission_ordinal
            self._admission_ordinal += 1
            rce = self.engine
            ceil = rce._stage_degraded_cap   # None = full capacity
            if self.adaptive_ring and rce.stage_limit is not None \
                    and rce.stage_slots_free < len(blocks) \
                    and (ceil is None or rce.stage_limit < ceil):
                # regrow on demand: re-open the ring (up to a degraded
                # recovery's sticky cap) BEFORE reserving, so the
                # adaptive clamp never fails or early-flushes an
                # admission the un-clamped ring could hold
                rce.set_stage_limit(ceil)
                self.ring_regrows += 1
                self._ring_window = []
                obs_metrics.inc("serve.ring_regrows")
            stage_ids = self.engine.stage_blocks(len(blocks))
            try:
                if self.fault_plan is not None:
                    # injection point for donation errors: fires AFTER the
                    # slots are reserved, simulating the prefill's donated
                    # staging buffers dying mid-call
                    self.fault_plan.check_admission(ordinal, self.engine)
                logits, k_stage, v_stage, extras = self._prefill_stage_jit(
                    self.params, batch, self.engine.pools["k_stage"],
                    self.engine.pools["v_stage"],
                    jnp.asarray(np.asarray(stage_ids, np.int32)))
            except Exception:
                # failed admission must not strand its staging slots.  The
                # staging pools are DONATED into the prefill call, so a
                # failure may have consumed them — then this admission
                # (and any earlier ones with queued promotions) lost its
                # staged bytes: evict it, and recover in place when asked
                self.engine.release_stage_blocks(stage_ids)
                dead = any(
                    getattr(self.engine.pools[n], "is_deleted",
                            lambda: False)()
                    for n in self.engine.staging)
                if dead:
                    self.free(sid)
                    self.evicted_sids.append(sid)
                    if self.auto_recover:
                        self.recover()
                raise
            # out-of-band prefill staging write (journal-exempt by
            # design, see docs/ARCHITECTURE.md "Failure model")
            self.engine.pools["k_stage"] = k_stage  # rowlint: disable=RC103
            self.engine.pools["v_stage"] = v_stage  # rowlint: disable=RC103
            # the promotion rides the round's serve stream (drained by
            # decode_round's stream.flush — one launch for the round)
            self._round_admitted_pages += len(stage_ids)
            pairs = list(zip(stage_ids, blocks))
            if self.dedup_admit:
                pairs = self._dedup_pages(sid, prompt, stage_ids, blocks)
            if pairs:
                stream.promote_staged(pairs)
            self._staged_sids.append(sid)
            self._pending_promotions[sid] = pairs
            st = extras
        else:
            # jitted like the fused path's prefill: an eager prefill rounds
            # differently and can flip a near-tie greedy argmax
            logits, st = self._prefill_jit(self.params, batch)
            # seed path: one ad-hoc gather/scatter dispatch per pool,
            # bypassing the command queue (kept for A/B)
            dst = jnp.asarray(np.asarray(blocks, np.int32))
            self.engine.alloc.mark_written(blocks)
            self.engine.pools["k"] = _stage_legacy(  # rowlint: disable=RC103
                self.engine.pools["k"], st["k_pools"], dst)
            notify_launch(len(blocks), 1, "legacy_stage")
            self.engine.pools["v"] = _stage_legacy(  # rowlint: disable=RC103
                self.engine.pools["v"], st["v_pools"], dst)
            notify_launch(len(blocks), 1, "legacy_stage")
        self.last_logits[sid] = np.asarray(logits[0])
        self.tokens[sid] = [int(t) for t in prompt]
        # extra per-seq state (ssm/hybrid/encdec) kept host-side per slot
        self._store_extra_state(sid, st)
        return sid

    def _dedup_pages(self, sid: int, prompt: np.ndarray,
                     stage_ids: List[int],
                     blocks: List[int]) -> List[Tuple[int, int]]:
        """Collapse this admission's prompt pages onto registered donor
        blocks where the chained fingerprints (and raw tokens) match.
        Returns the surviving (stage slot, block) promotion pairs; matched
        pages share the donor by refcount, their staging slots return to
        the ring, and unmatched pages register as future donors (the
        registry holds its own refcount on each donor block, so a donor's
        bytes outlive CoW splits and frees of any individual sharer)."""
        seq = self.cache.seqs[sid]
        page = self.cache.page
        new_blocks = list(blocks)
        keep: List[Tuple[int, int]] = []
        released: List[int] = []
        registered: List[int] = []
        chain = 0
        for j, b in enumerate(blocks):
            toks = tuple(int(t) for t in prompt[j * page:(j + 1) * page])
            chain = page_fingerprint(chain, toks)
            hit = self._dedup_registry.get(chain)
            if hit is not None and hit[1] == toks and (
                    self.cache.batch_groups == 1
                    or self.cache.group_of_block(hit[0]) == seq.group):
                donor = hit[0]
                self.engine.alloc.share([donor])
                new_blocks[j] = donor
                released.append(stage_ids[j])
                self.dedup_pages_shared += 1
                self.dedup_bytes_saved += self.engine._block_bytes()
            else:
                keep.append((stage_ids[j], b))
                if hit is None:
                    # register as a donor: the registry's own refcount
                    # pins the block (and its promoted bytes) while the
                    # entry lives
                    self.engine.alloc.share([b])
                    self._dedup_registry[chain] = (b, toks)
                    registered.append(chain)
        if registered:
            self._dedup_keys[sid] = registered
        if released:
            self.dedup_hits += 1
            self.engine.release_stage_blocks(released)
            self.cache.remap_blocks(sid, new_blocks)
        return keep

    def kv_bytes_live(self) -> int:
        """Primary-pool KV bytes backed by currently-allocated blocks —
        the dedup-on-admit headline: admissions whose prompt pages
        collapse onto shared donor blocks grow this by less than their
        page count (``BENCH_dispatch.json`` v8 ``dedup_admit`` leg)."""
        alloc = self.engine.alloc
        used = alloc.num_blocks - alloc.total_free()
        return used * self.engine._block_bytes()

    def _store_extra_state(self, sid, st):
        extras = {}
        for k in ("conv_state", "ssm_state", "cross_k", "cross_v"):
            if k in st:
                extras[k] = st[k]
        if extras:
            self._extras[sid] = extras

    def fork(self, sid: int, n: int) -> List[int]:
        """CoW-fork ``sid`` into ``n`` children (parallel sampling / beam
        search): prompt pages share by refcount — zero bytes move.  Any
        eager cross-group copies a sharded-batch fork needs are captured
        onto the serve stream (they drain with the round)."""
        if self.fused_staging:
            with self.stream.capture():
                kids = self.cache.fork(sid, n)
        else:
            kids = self.cache.fork(sid, n)
        for c in kids:
            self.last_logits[c] = self.last_logits[sid].copy()
            self.tokens[c] = list(self.tokens[sid])
            if sid in self._extras:
                self._extras[c] = self._extras[sid]
        return kids

    def free(self, sid: int) -> None:
        """Release a finished sequence's blocks, slot, and host state —
        including lifecycle state a mid-round free would otherwise leak:

        * a still-queued stage→KV promotion is RETIRED (the rows leave
          the command queues without dispatching and the staging slots
          return to the ring) — otherwise the stale promotion lands in
          blocks the allocator may have re-issued to a NEWER sequence,
          silently corrupting its KV pages;
        * the sid leaves ``_staged_sids`` so a later recovery does not
          "evict" a sequence that no longer exists;
        * the ``_extras`` entry (conv/ssm/cross-attention host state) is
          dropped — previously it accumulated forever under churn;
        * dedup-on-admit registry entries this sid registered are
          invalidated (their registry refcount released) so no future
          admission can match a donor whose bytes may recycle — and a
          queued promotion into a block a LIVE dupe still shares is kept
          queued rather than retired: the dupe's page depends on exactly
          that write landing;
        * a DEMOTED sid releases its spill parking slots instead (no
          cache sequence exists for it)."""
        parked = self.demoted.pop(sid, None)
        if parked is not None:
            self.engine.release_spill_slots(parked.slots)
            self._extras.pop(sid, None)
            return
        for key in self._dedup_keys.pop(sid, []):
            blk, _ = self._dedup_registry.pop(key)
            self.engine.alloc.free([blk])
        pending = self._pending_promotions.pop(sid, None)
        if pending:
            if self.dedup_admit:
                # with the registry's refs gone, refcount > 1 on a dst
                # means a live dupe shares it — its staged write must
                # still land (the block cannot recycle while the dupe
                # holds it)
                pending = [(s, d) for s, d in pending
                           if not self.engine.alloc.is_shared(d)]
            if pending:
                self.engine.retire_promotions(pending)
        if sid in self._staged_sids:
            self._staged_sids.remove(sid)
        self.cache.free_sequence(sid)
        self.last_logits.pop(sid, None)
        self.tokens.pop(sid, None)
        self._extras.pop(sid, None)

    # ------------------------------------------------------------------
    def demote(self, sid: int, stream=None) -> None:
        """Preempt ``sid``: park its KV bytes in spill slots
        (``OP_CROSS_POOL_COPY``, the reverse of admission promotion) and
        release its batch slot + blocks — :meth:`resume` brings it back
        bitwise-identically.  Needs ``spill_pages`` capacity.

        The victim's blocks stay allocated until the round's flush
        drains the demote reads (``_free_after_flush``): freeing them
        immediately would let a same-round admission reuse a block whose
        demote read is still pending — the cross-stream WAR guard would
        force an early drain (an extra launch) to stay correct.  CoW
        forks are handled naturally: the parked copy is private, and
        siblings keep their shared refcounts.

        ``stream`` routes the demote copies onto a caller stream (a
        scheduler lane); default is the serve stream."""
        if sid in self._staged_sids:
            raise RuntimeError(
                f"cannot demote seq {sid}: its admission promotion has "
                "not drained yet (preempt it next round)")
        stream = self.stream if stream is None else stream
        seq = self.cache.seqs[sid]
        blocks = list(seq.blocks)
        # decode writes pool bytes inside the jit, out of band of the
        # allocator's ZI metadata — mark them written so the demote copy
        # moves the real bytes instead of re-materializing zeros
        self.engine.alloc.mark_written(blocks)
        slots = stream.demote_to_spill(blocks)
        self.demoted[sid] = DemotedSeq(
            length=seq.length, slots=list(slots), slab_home=seq.slab_home,
            logits=self.last_logits.pop(sid),
            tokens=self.tokens.pop(sid, []),
            extras=self._extras.pop(sid, None))
        # keep the blocks alive past free_sequence (share +1 / free -1)
        # and release the extra ref only after the flush
        self.engine.alloc.share(blocks)
        self.cache.free_sequence(sid)
        self._free_after_flush.extend(blocks)

    def resume(self, sid: int, stream=None) -> int:
        """Un-park a demoted sequence: allocate fresh blocks (same slab
        affinity), enqueue the spill→KV promotion, and restore the host
        state under a NEW sid (returned — callers map request→sid).
        Greedy decode from the resumed state is bitwise-identical to the
        unpreempted run (the parked bytes ARE the KV pages)."""
        d = self.demoted.pop(sid)
        stream = self.stream if stream is None else stream
        with stream.capture():
            new_sid = self.cache.new_sequence(prompt_len=d.length,
                                              prefer_slab=d.slab_home)
        blocks = self.cache.blocks_of(new_sid)
        assert len(blocks) == len(d.slots), (len(blocks), len(d.slots))
        stream.promote_spilled(list(zip(d.slots, blocks)))
        self.last_logits[new_sid] = d.logits
        self.tokens[new_sid] = d.tokens
        if d.extras is not None:
            self._extras[new_sid] = d.extras
        self._resumed.append((new_sid, list(d.slots)))
        return new_sid

    # ------------------------------------------------------------------
    def recover(self) -> RecoveryReport:
        """Return the serving engine to a clean state after a failed
        flush, ckpt tick, or donated-admission error.

        Wraps ``RowCloneEngine.recover`` with serving policy: the latest
        pool checkpoint (when one exists) restores dead KV pools; a dead
        double-buffered staging ring comes back at SINGLE-buffer capacity
        (the degraded mode — bursts drain early instead of parking in the
        poisoned shadow half); and admissions whose staged bytes were
        lost (dead staging, or promotions evicted from the queues) are
        freed into ``evicted_sids`` — re-admitting their prompts
        reproduces the KV bytes, so greedy decode stays bitwise-identical
        to a failure-free run.  Aborted flushes' suffixes re-drain inside
        the engine call (retry/backoff), completing promotions that were
        already dispatched rather than evicting them."""
        eng = self.engine
        staging_dead = any(
            getattr(eng.pools[n], "is_deleted", lambda: False)()
            for n in eng.staging)
        # probe spill-pool death BEFORE the engine resurrects the pools:
        # dead spill pools take every demoted sequence's parked bytes
        # with them
        spill_dead = any(
            getattr(eng.pools[s.name], "is_deleted", lambda: False)()
            for s in eng.group if s.role == "spill") if self.spill_pages \
            else False
        degraded = None
        if staging_dead and self.double_buffer:
            degraded = self.ring_capacity
        snap = self.pool_ckpt.latest() if self.pool_ckpt is not None \
            else None
        rep = eng.recover(snapshot=snap,
                          degraded_stage_capacity=degraded)
        if self.pool_ckpt is not None:
            self.pool_ckpt.reset()
        if staging_dead or rep.evicted_promotions:
            # the staged bytes backing these admissions never reached the
            # KV pools (and are unrecoverable): evict for re-admission
            for sid in list(self._staged_sids):
                if sid in self.cache.seqs:
                    self.free(sid)
                    self.evicted_sids.append(sid)
        # demoted victims' blocks: the aborted queues dropped the demote
        # reads, so the deferred frees happen NOW (release the extra ref)
        if self._free_after_flush:
            eng.alloc.free(self._free_after_flush)
            self._free_after_flush = []
        # in-flight resumes: their spill→KV promotions may have been
        # aborted with the queues — evict for re-admission (same contract
        # as staged admissions); release_spill_slots is idempotent, so
        # slots already reclaimed by an earlier drain are skipped
        for sid, slots in self._resumed:
            if sid in self.cache.seqs:
                self.free(sid)
                self.evicted_sids.append(sid)
            eng.release_spill_slots(slots)
        self._resumed = []
        if spill_dead:
            # the parked KV bytes died with the spill pools: evict every
            # demoted sequence for re-admission
            for sid in list(self.demoted):
                self.free(sid)
                self.evicted_sids.append(sid)
        self._staged_sids = []
        self._pending_promotions.clear()
        self.last_ticket = None
        self.last_recovery = rep
        return rep

    # ------------------------------------------------------------------
    def _post_flush(self) -> None:
        """Round-boundary bookkeeping after the stream flush drained the
        round's bulk movement: staged admissions and resumed sequences
        are no longer in flight, demoted victims' blocks (whose demote
        reads just drained) go back to the allocator, and the adaptive
        staging-ring controller takes its per-round sample."""
        self._staged_sids = []
        self._pending_promotions.clear()
        self._resumed = []
        if self._free_after_flush:
            self.engine.alloc.free(self._free_after_flush)
            self._free_after_flush = []
        eng = self.engine
        if not eng.staging:
            return
        effective = eng.stage_limit if eng.stage_limit is not None \
            else eng.stage_capacity
        in_use = eng.stage_capacity - eng.stage_slots_free \
            - len(eng._stage_parked)
        obs_metrics.set_gauge("serve.ring_occupancy", in_use)
        obs_metrics.set_gauge("serve.ring_limit", effective)
        if not self.adaptive_ring:
            return
        self._ring_window.append(self._round_admitted_pages)
        self._round_admitted_pages = 0
        if len(self._ring_window) < self.RING_WINDOW:
            return
        peak = max(self._ring_window)
        self._ring_window = []
        # sustained low pressure: a whole window peaked at <= half the
        # usable ring -> clamp to 2x that peak (regrow-on-demand covers
        # any later burst; never below one slot)
        if effective > 1 and peak <= effective // 2:
            new_limit = max(2 * peak, 1)
            if new_limit < effective:
                eng.set_stage_limit(new_limit)
                self.ring_shrinks += 1
                obs_metrics.inc("serve.ring_shrinks")

    def _decode_fn(self, params, k_pools, v_pools, table, mask, base,
                   seq_lens, tokens, slot_index):
        state = {"k_pools": k_pools, "v_pools": v_pools,
                 "block_table": table, "share_mask": mask, "base": base,
                 "seq_lens": seq_lens}
        logits, st = self.model.decode_step(params, state, tokens, self.mesh,
                                            impl=self.impl)
        return logits, st["k_pools"], st["v_pools"]

    def decode_round(self, sample_fn=None) -> Dict[int, int]:
        """One token for every live sequence (greedy by default)."""
        if self.cfg.family not in ("dense", "moe", "vlm"):
            raise NotImplementedError(
                "CLI decode loop demo targets decoder-only archs; other "
                "families decode through model.decode_step directly")
        live = sorted(self.cache.seqs)
        if not live:
            # still drain pending bulk movement (e.g. every sequence was
            # demoted this round): the parked bytes must land and the
            # deferred block frees must happen even with nothing to decode
            if len(self.stream.queue):
                try:
                    self.last_ticket = self.stream.flush()
                except Exception:
                    if not self.auto_recover:
                        raise
                    self.recover()
                self._post_flush()
            return {}
        # choose next token per sequence from last logits
        next_tok = {}
        for sid in live:
            lg = self.last_logits[sid]
            t = int(np.argmax(lg)) if sample_fn is None else sample_fn(lg)
            next_tok[sid] = t
        # CoW/allocation happens BEFORE the jit step (host metadata); the
        # round's staged-prefill promotions + CoW splits + tail-block
        # inits all drain as ONE fused launch at this stream flush —
        # the FlushTicket records the round's launch accounting
        if self.fused_staging:
            with self.stream.capture():
                self.cache.append_tokens(live)
        else:
            self.cache.append_tokens(live)   # seed path: eager per-call
        try:
            self.last_ticket = self.stream.flush()
        except Exception:
            if not self.auto_recover:
                raise
            # recover in place: the aborted flush's suffix re-drains
            # inside recover() (same rows, same bytes), so this round's
            # decode proceeds normally and tokens match the clean run
            self.recover()
            # a recovery may have evicted admissions; decode the rest
            live = [s for s in live if s in self.cache.seqs]
            next_tok = {s: next_tok[s] for s in live}
            if not live:
                return {}
        self._post_flush()
        table, mask, base = self.cache.device_tables()
        lens = self.cache.seq_lens()
        B = self.cache.max_seqs
        toks = np.zeros((B,), np.int32)
        seq_lens_dev = np.zeros((B,), np.int32)
        for sid in live:
            slot = self.cache.slot_of(sid)
            toks[slot] = next_tok[sid]
            # decode_step's pos = state.seq_lens = position of new token
            seq_lens_dev[slot] = self.cache.seqs[sid].length - 1
        logits, kp, vp = self._decode_jit(
            self.params, self.engine.pools["k"], self.engine.pools["v"],
            table, mask, base, jnp.asarray(seq_lens_dev), jnp.asarray(toks),
            None)
        # out-of-band decode-step append (reproduced by re-running the
        # producer on recovery, never by journal replay)
        self.engine.pools["k"] = kp  # rowlint: disable=RC103
        self.engine.pools["v"] = vp  # rowlint: disable=RC103
        logits = np.asarray(logits)
        for sid in live:
            slot = self.cache.slot_of(sid)
            self.last_logits[sid] = logits[slot]
            self.tokens[sid].append(next_tok[sid])
        if self.pool_ckpt is not None:
            # one background checkpoint window per round: spill-pool
            # cross-copies on the ckpt stream, harvested next round (the
            # ticket's write-scoped wait never blocks on the KV pools
            # this round's decode just donated)
            try:
                self.pool_ckpt.step()
            except Exception:
                if not self.auto_recover:
                    raise
                self.recover()
        return next_tok


@jax.jit
def _stage_legacy(pool, staging, dst_ids):
    """SEED staging path (``fused_staging=False`` A/B only): scatter the
    prefill's pages (L, nper, ...) straight into the KV pool, one ad-hoc
    dispatch per pool, bypassing the command queue."""
    safe = jnp.where(dst_ids >= 0, dst_ids, pool.shape[1])
    return pool.at[:, safe].set(staging.astype(pool.dtype), mode="drop")


#: pool sizing shared by the CLI and the chip smoke: 16 live sequences of
#: up to 16 pages (1024 tokens) each.  The staging ring derives from it
#: (one admission of ``SERVE_BLOCKS_PER_SEQ`` pages per round).
SERVE_MAX_SEQS = 16
SERVE_BLOCKS_PER_SEQ = 16


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at one fixed directory and
    return it: ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads the
    variable itself, so nothing else is set), else ``.jax_cache`` at the
    root of the checkout.  Entry points call this at start-up; importing
    the library never does."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(pathlib.Path(__file__).resolve().parents[3]
                   / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def init_params(cfg, seed: int, sharding=None):
    """Random weights for ``cfg`` from ``seed``, in ``cfg.dtype``.  The
    init is jitted and casts inside, so a full-width fp32 tree (12.8 GB
    for llama3.2-3b) never exists on the device.  ``sharding`` places
    every leaf (e.g. replicated over a serving mesh)."""
    model = build_model(cfg)
    dtype = jnp.dtype(cfg.dtype)

    def init(key):
        params, _ = split_params(model.init_params(key))
        return jax.tree_util.tree_map(lambda x: x.astype(dtype), params)

    return jax.jit(init, out_shardings=sharding)(jax.random.key(seed))


def build_engine(arch: str, *, full: bool = False, seed: int = 0,
                 mesh=None, **engine_kw) -> ServingEngine:
    """The serving engine the CLI and ``chip_smoke.py`` run: ``arch`` at
    its published widths (``full``) or its reduced smoke variant, random
    weights from ``seed`` (replicated over ``mesh`` when given), and
    explicitly sized pools.  ``engine_kw`` passes through to
    :class:`ServingEngine`."""
    cfg = get_config(arch)
    if not full:
        cfg = cfg.reduced()
    sharding = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        sharding = NamedSharding(mesh, PartitionSpec())
    params = init_params(cfg, seed, sharding)
    return ServingEngine(cfg, params, mesh=mesh, max_seqs=SERVE_MAX_SEQS,
                         max_blocks_per_seq=SERVE_BLOCKS_PER_SEQ, **engine_kw)


def print_memory_report(eng: ServingEngine, tag: str = "serve") -> None:
    """Print the bytes the engine holds on the device: the weights, and
    each pool with its shape."""
    params = sum(x.nbytes for x in jax.tree_util.tree_leaves(eng.params))
    print(f"[{tag}] params: {params} bytes ({eng.cfg.arch_id}, "
          f"{eng.cfg.dtype})")
    for name, pool in eng.engine.pools.items():
        print(f"[{tag}] pool {name}: {tuple(pool.shape)} {pool.dtype} = "
              f"{pool.nbytes} bytes")
    print(f"[{tag}] pools total: {eng.engine.pool_bytes_resident()} bytes "
          f"({eng.cache.max_seqs} seqs x {eng.cache.max_blocks_per_seq} "
          f"blocks, {eng.engine.stage_capacity} staging slots)")


def main():
    """CLI: admit random prompts, optionally fork, greedy-decode, and
    print the RowClone mechanism stats (see the module docstring)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--fork", type=int, default=0)
    ap.add_argument("--full", action="store_true",
                    help="published widths in the config's dtype "
                         "(default: the reduced smoke variant)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--staging-ring", type=int, default=-1,
                    help="staging slots (max_admit_pages): size staging "
                         "as a recycled ring instead of full KV twins "
                         "(~2x less resident pool memory); 0 = full "
                         "twin, -1 = derive from the admission policy")
    ap.add_argument("--double-buffer", action="store_true",
                    help="double-buffered staging ring: admission bursts "
                         "past the ring capacity park in the shadow half "
                         "at 1.0 launches/round")
    args = ap.parse_args()

    setup_compile_cache()
    eng = build_engine(args.arch, full=args.full, seed=args.seed,
                       max_admit_pages=(None if args.staging_ring < 0
                                        else args.staging_ring),
                       double_buffer=args.double_buffer)
    cfg = eng.cfg
    print_memory_report(eng)
    rng = np.random.default_rng(args.seed)
    sids = []
    for i in range(args.requests):
        p = rng.integers(2, cfg.vocab_size, size=args.prompt_len)
        sid = eng.add_request(p.astype(np.int32))
        sids.append(sid)
        print(f"[serve] admitted seq {sid} ({args.prompt_len} tokens)")
    if args.fork:
        kids = eng.fork(sids[0], args.fork)
        print(f"[serve] forked seq {sids[0]} -> {kids} "
              f"(CoW shares: {eng.engine.alloc.stats.cow_shares})")
    with obs_metrics.Stopwatch() as sw:
        for step in range(args.steps):
            eng.decode_round()
    dt = sw.s
    n_live = len(eng.cache.seqs)
    print(f"[serve] {args.steps} rounds x {n_live} seqs in {dt:.2f}s "
          f"({args.steps * n_live / dt:.1f} tok/s)")
    s = eng.engine.stats
    print(f"[serve] rowclone: fpm={s.fpm_copies} psm={s.psm_copies} "
          f"alias={s.alias_copies} lazy-zero={s.zero_lazy} "
          f"bytes_avoided={s.bytes_avoided}")


if __name__ == "__main__":
    main()
