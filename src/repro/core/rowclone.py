"""RowCloneEngine — the ``memcopy``/``meminit`` "ISA" and its dispatcher.

Paper §2.3: software issues ``memcopy``/``meminit``; the microarchitecture
decides per request whether FPM, PSM, or the ordinary path applies, and the
MC serializes the commands.  Here:

* ``memcopy(pairs)``  — partitions (src, dst) block pairs by placement:
    - ``alias``  : dst unwritten + ZI enabled → refcount bump only
                   (in-cache copy: zero bytes move)
    - ``fpm``    : same slab → subarray-local DMA copy
    - ``psm``    : cross-slab → serialized transfer (ICI path)
    - ``baseline``: RowClone disabled → copy through the compute pipeline
* ``meminit(ids)``    — ZI lazy-zero bit when possible, else the zero-row
                        DMA broadcast.

Dispatch is **queued and fused** (core/cmdqueue.py): classification tags
each request with an opcode and enqueues it; at a flush boundary the whole
table drains as ONE fused kernel launch moving every pool
(kernels/fused_dispatch.py) — the MC command-drain analogue, with the
DMA wait trailing one step behind issue (the overlapped drain; the
queue's source-hazard tracking keeps adjacent table rows disjoint).

Asynchrony is a first-class surface (core/stream.py): ``engine.stream()``
mints an ordered :class:`~repro.core.stream.CommandStream`; commands
enqueued on it drain only at ``stream.flush()``, which returns a
:class:`~repro.core.stream.FlushTicket` (launch accounting, drained
command count, post-drain block state on demand).  Streams serialize
against each other only when they touch the same ``(pool, block)`` (the
cross-stream guard).  The seed-era surface is a thin wrapper over the
engine's DEFAULT stream: each public call flushes on return (eager,
seed-compatible semantics); inside ``with engine.batch():`` commands
accumulate and the device sees a single launch at exit — the
attention-step / benchmark-tick boundary.

Tables pad to power-of-two buckets (8/32/128/512, overflow chunked), not the
seed's fixed ``max_requests`` length.  Under a multi-device mesh the flush
drains as ONE shard_map'd collective launch: the table is partitioned into
per-slab sub-tables (slab-local ids, same kernel) plus a cross-slab
send/recv plan executed with ppermute inside the same launch
(core/cmdqueue.py ``partition_commands``).  ``use_fused=False`` keeps the
seed's per-mechanism, per-pool fan-out (one jit'd call per pool per
mechanism, padded to ``max_requests``) for A/B benchmarking; on sharded
arrays those global gather/scatters compile through GSPMD.

Addressing is the engine's :class:`~repro.core.poolspec.PoolGroup`: every
pool has its OWN block count, cross-pool commands carry global
``base[pool] + block`` ids (prefix-sum bases), and public calls accept
:class:`~repro.core.poolspec.BlockRef` operands — which is what lets a
serving engine size its staging pools as a small recycling ring instead of
full-size KV twins (~2x less resident pool memory, see launch/serve.py).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
import warnings
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core.allocator import SubarrayAllocator
from repro.core.cmdqueue import (BITWISE_OPS, CommandQueue, OP_AND,
                                 OP_BASELINE_COPY, OP_CROSS_POOL_COPY,
                                 OP_FPM_COPY, OP_NOP, OP_NOT, OP_OR,
                                 OP_PSM_COPY, OP_ZERO_INIT, bucket_size,
                                 pack_bitwise_src, partition_commands,
                                 space_war_rows, top_bucket,
                                 unpack_bitwise_src)
from repro.core.journal import (AbortedFlush, JournalRecord, PoolSnapshot,
                                RecoveryError, RecoveryReport, TicketJournal)
from repro.core.opcodes import (ALL_PRIMARY, OPCODE_NAMES, check_pack_total,
                                opspec, row_rw)
from repro.core.poolspec import BlockRef, PoolGroup
from repro.core.sanitizer import DrainSanitizer, sanitize_enabled
from repro.core.stream import CommandStream
from repro.kernels import ops as kops
# module import, not names: the kernel module imports core.opcodes, so
# importing it first must not need its names while core initializes
from repro.kernels import fused_dispatch as kfd
from repro.models.paged import pool_shard_axes, pool_shard_count
from repro.obs import metrics as obs_metrics
from repro.obs.autotune import load_profile
from repro.obs.trace import FlushTiming, span


@dataclasses.dataclass
class EngineStats:
    fpm_copies: int = 0
    psm_copies: int = 0
    alias_copies: int = 0
    baseline_copies: int = 0
    cross_pool_copies: int = 0
    stage_promotions: int = 0   # staged blocks promoted into primary pools
    retired_promotions: int = 0  # queued promotions cancelled pre-flush
    demotions: int = 0          # primary blocks parked in spill slots
    spill_promotions: int = 0   # spill slots promoted back into primaries
    zero_lazy: int = 0
    zero_materialized: int = 0
    bytes_fpm: int = 0
    bytes_psm: int = 0
    bytes_baseline: int = 0
    bytes_cross: int = 0
    bytes_avoided: int = 0      # alias + lazy zero
    cross_stream_flushes: int = 0  # streams serialized by an overlap
    launches: int = 0           # device dispatches issued for bulk movement
    bitwise_ops: int = 0        # AND/OR/NOT compute rows enqueued
    bytes_bitwise: int = 0      # destination bytes written by bitwise rows


class RowCloneEngine:
    """Owns block pools + allocator; dispatches copy/init requests.

    ``pools`` is a dict name -> jnp array (nblk_p, ...) — e.g. {"k":
    k_pools, "v": v_pools} sharing one allocator (paired pools: a request
    applies to every pool, like K and V pages of one KV block).  The
    engine's address space is its :class:`~repro.core.poolspec.PoolGroup`
    (``engine.group``): per-pool block counts with prefix-sum base
    offsets, so staging pools may be sized independently of their KV
    twins (a small staging *ring* instead of a full-size twin).  Public
    copy calls address blocks with :class:`~repro.core.poolspec.BlockRef`;
    bare ints remain accepted as primary-address-space ids.
    ``memcopy_cross`` takes (BlockRef, BlockRef) pairs only — the
    pool-name keyword shim is gone.
    """

    def __init__(self, pools: Dict[str, jnp.ndarray],
                 allocator: SubarrayAllocator,
                 mesh: Optional[Mesh] = None,
                 enable_fpm: bool = True, enable_psm: bool = True,
                 enable_zi: bool = True, max_requests: int = 256,
                 block_axis: int = 0, use_fused: bool = True,
                 staging: Optional[Dict[str, str]] = None,
                 group: Optional[PoolGroup] = None,
                 sanitize: Optional[bool] = None,
                 overlap: Optional[bool] = None):
        """``block_axis``: which pool axis indexes blocks.  0 = flat pools
        (nblk, ...); 1 = layer-stacked serving pools (L, nblk, ...) where a
        logical block is L physical pages moved together (L independent
        DMAs per request on TPU).

        ``use_fused``: drain flushed command tables through the single
        fused-dispatch launch (default) — under a multi-device mesh, one
        shard_map'd collective launch over per-slab sub-tables.  False
        restores the seed's per-mechanism, per-pool fan-out padded to
        ``max_requests``, kept for A/B benchmarking.

        ``group``: the engine's :class:`PoolGroup` address space.  When
        omitted, one is built from the arrays + the ``staging`` map (a
        staging pool name -> paired primary pool dict, e.g.
        ``{"k_stage": "k", "v_stage": "v"}``), with each pool's ``nblk``
        read off its block axis.  Primary pools must match the allocator's
        block count; staging pools may be ANY size (all staging pools
        share one size — the promotion slot space) but must mirror their
        twin's block shape and dtype.  Plain opcodes (memcopy/meminit)
        move blocks in primary pools only; staged bytes enter and leave a
        staging pool exclusively through ``OP_CROSS_POOL_COPY``
        (``promote_staged``), so allocator metadata (ZI bits, refcounts)
        keeps describing primary blocks.  Staging slot ids are
        engine-managed (``stage_blocks``), disjoint from the allocator's
        free lists.

        ``sanitize``: attach the TSAN-style drain sanitizer
        (core/sanitizer.py) — every flushed chunk is validated against
        the opcode contract registry before its donating launch (operand
        decode, staging legality, NOP well-formedness, RAW/WAW absence,
        WAR adjacency, ShardPlan partitioning) and shadow-executed
        through the jnp oracle on host copies with a bitwise diff.
        ``None`` (the default) reads the ``REPRO_SANITIZE`` env var.  The
        sanitizer issues no extra device launches, so launch accounting
        (and the 1-launch-per-flush gates) is unchanged.

        ``overlap``: the fused Pallas drain's overlapped-DMA toggle.
        ``None`` (the default) resolves through this backend's
        :class:`~repro.obs.autotune.TunedProfile` when one is committed
        under ``configs/tuned/`` (kwarg > profile > built-in True) —
        per-engine autotuned knobs apply here; process-wide ones
        (bucket set, delta-signature bound) only via the explicit
        ``repro.obs.autotune.apply_profile``."""
        self.alloc = allocator
        self.mesh = mesh
        self.enable_fpm = enable_fpm
        self.enable_psm = enable_psm
        self.enable_zi = enable_zi
        self.max_requests = max_requests
        self.block_axis = block_axis
        self.use_fused = use_fused
        #: this backend's committed TunedProfile, or None (obs/autotune.py)
        self.profile = load_profile()
        if overlap is None:
            overlap = self.profile.overlap if self.profile is not None \
                else True
        self.overlap = bool(overlap)
        #: FlushTiming of the most recent drain (FlushTicket.timing source)
        self.last_drain_timing: Optional[FlushTiming] = None
        if group is None:
            group = PoolGroup.from_pools(pools, block_axis=block_axis,
                                         staging=staging)
        self.group = group
        self.staging = dict(group.staging_map)
        assert set(group.names) == set(pools), (group.names, list(pools))
        # group order is the table order everywhere — realign the dict
        self.pools = {name: pools[name] for name in group.names}
        self.stats = EngineStats()
        if sanitize is None:
            sanitize = sanitize_enabled()
        #: the attached drain sanitizer, or None (core/sanitizer.py)
        self.sanitizer: Optional[DrainSanitizer] = \
            DrainSanitizer(self) if sanitize else None
        # every engine owns a DEFAULT CommandStream: the seed-era public
        # calls (memcopy/flush/batch) are thin wrappers over it; callers
        # wanting explicit asynchrony mint more with stream().  The
        # engine tracks only queues with PENDING work (registered on
        # enqueue, dropped when drained), so minting streams is free:
        # no registry growth, and the cross-stream guard scans only
        # queues that could actually conflict.
        self._live_queues: Dict[int, CommandQueue] = {}
        self._stream_count = 0
        self._default_stream = CommandStream(self, "default")
        self._cur_queue = self._default_stream.queue
        self.deferred = False
        self._warned_unshardable = False
        self._zero_blocks: Optional[Tuple[jnp.ndarray, ...]] = None
        nblk = allocator.num_blocks
        for spec in group:
            p = self.pools[spec.name]
            assert p.shape[block_axis] == spec.nblk, \
                f"pool {spec.name!r}: {p.shape[block_axis]} blocks != " \
                f"spec nblk {spec.nblk}"
            if spec.role == "primary":
                assert spec.nblk == nblk, \
                    f"primary pool {spec.name!r}: {spec.nblk} blocks != " \
                    f"allocator's {nblk}"
        stage_cap = 0
        for sname, pname in self.staging.items():
            s, p = self.pools[sname], self.pools[pname]
            s_blk = list(s.shape)
            cap = s_blk.pop(block_axis)
            p_blk = list(p.shape)
            p_blk.pop(block_axis)
            assert s_blk == p_blk and s.dtype == p.dtype, \
                f"staging pool {sname!r} must mirror {pname!r}'s block " \
                "shape and dtype"
            assert stage_cap in (0, cap), \
                "staging pools must share one block count (the promotion " \
                f"slot space): {stage_cap} != {cap}"
            stage_cap = cap
        for spec in group:
            if spec.role != "spill":
                continue
            s, p = self.pools[spec.name], self.pools[spec.paired]
            s_blk = list(s.shape)
            s_blk.pop(block_axis)
            p_blk = list(p.shape)
            p_blk.pop(block_axis)
            assert s_blk == p_blk and s.dtype == p.dtype, \
                f"spill pool {spec.name!r} must mirror {spec.paired!r}'s " \
                "block shape and dtype"
        # staging slot free list + ids whose promotion is still queued
        # (reclaimed by _after_flush once no stream holds a pending READ
        # of the slot — the queues' source-hazard tracking)
        self._stage_free: List[int] = list(range(stage_cap - 1, -1, -1))
        self._stage_inflight: List[int] = []
        # slots parked above the adaptive ring limit (set_stage_limit):
        # excluded from stage_blocks until the limit is raised again
        self._stage_parked: List[int] = []
        # a degraded recover()'s sticky ring cap: the adaptive ring may
        # shrink below it but regrow-on-demand never exceeds it
        self._stage_degraded_cap: Optional[int] = None
        # preemption demotion: primary pool name -> its spill twin, plus
        # the engine-owned demotion slot space (a sub-range of the spill
        # pools handed over by enable_demotion — the rest of the spill
        # pools stays free for e.g. checkpoint windows)
        self._spill_map: Dict[str, str] = {
            spec.paired: spec.name for spec in group
            if spec.role == "spill"}
        self._spill_slots: Tuple[int, ...] = ()
        self._spill_free: List[int] = []
        self._spill_inflight: List[int] = []
        #: replayable flush log — every drained flush appends one record
        self.journal = TicketJournal()
        self._flush_index = 0
        self._last_plan_sig: Optional[Tuple] = None
        self._aborted: List[AbortedFlush] = []
        self._stage_limit: Optional[int] = None
        # frozen per-pool layout (shape, dtype, sharding) so recover()
        # can resurrect or restore buffers with the original placement;
        # uncommitted single-device pools record no sharding — pinning
        # them via device_put would commit the restored buffer and break
        # the mesh drain's shard_map placement
        self._pool_layouts = {
            name: (tuple(p.shape), p.dtype, self._pool_placement(p))
            for name, p in self.pools.items()}

    @staticmethod
    def _pool_placement(p):
        """The sharding recover() should restore ``p`` under, or None.
        Only committed multi-device placements are pinned: an uncommitted
        (or single-device) array must be restored uncommitted so jit/
        shard_map remains free to place it."""
        sh = getattr(p, "sharding", None)
        if sh is None or not getattr(p, "_committed", True):
            return None
        if len(getattr(sh, "device_set", ())) <= 1:
            return None
        return sh

    # ------------------------------------------------------------------
    # streams
    # ------------------------------------------------------------------
    def _note_pending(self, queue: CommandQueue) -> None:
        """A queue gained pending work: track it for the cross-stream
        guard, staging-slot reclaim, and engine-wide drains (called by
        CommandQueue.enqueue)."""
        self._live_queues[id(queue)] = queue

    def _note_drained(self, queue: CommandQueue) -> None:
        """A queue drained to empty: drop it from the live set (called by
        CommandQueue.flush) — drained streams cost nothing, however many
        a caller mints."""
        self._live_queues.pop(id(queue), None)

    def stream(self, name: Optional[str] = None) -> CommandStream:
        """Mint a new ordered :class:`CommandStream` on this engine.

        Commands enqueued on it do NOT flush on return; ``stream.flush()``
        drains them and returns a :class:`FlushTicket`.  Streams are
        unordered against each other until they touch the same
        ``(pool, block)`` — then the earlier stream drains first (the
        cross-stream guard), so conflicts serialize at block granularity
        instead of a global barrier.  Minting is cheap and streams need
        no close(): the engine only tracks queues while they hold
        pending commands."""
        self._stream_count += 1
        if name is None:
            name = f"stream{self._stream_count}"
        return CommandStream(self, name)

    @property
    def queue(self) -> CommandQueue:
        """The DEFAULT stream's command queue (seed-compatible surface —
        public engine calls enqueue here unless captured by a stream)."""
        return self._default_stream.queue

    @property
    def default_stream(self) -> CommandStream:
        """The engine's default :class:`CommandStream` (what ``batch()``/
        ``flush()`` wrap)."""
        return self._default_stream

    def _cross_stream_guard(self, queue: CommandQueue,
                            skeys, dkey) -> None:
        """Serialize streams that touch the same blocks: a command about
        to land on ``queue`` that reads or writes another stream's pending
        WRITE, or writes another stream's pending READ, drains that other
        stream first.  (Reading another stream's pending read is harmless
        — RAR.)  ``skeys`` is the tuple of read keys — two-source bitwise
        rows contribute both decoded sources, so a conflict on EITHER
        source drains the other stream.  Flush order between unrelated
        streams stays undefined, which is the asynchrony the API sells.
        Only queues with pending work are scanned (the live set)."""
        for q in list(self._live_queues.values()):
            if q is queue or not len(q):
                continue
            clash = q.has_pending_write(dkey) or q.has_pending_read(dkey) \
                or any(q.has_pending_write(k) for k in skeys)
            if clash:
                self.stats.cross_stream_flushes += 1
                q.flush()

    # ------------------------------------------------------------------
    @property
    def num_blocks(self) -> int:
        """Blocks per PRIMARY pool (the allocator's address space; staging
        pools size independently — see ``stage_capacity``)."""
        return self.alloc.num_blocks

    @property
    def stage_capacity(self) -> int:
        """Staging slot ids available per staging pool (0 = no staging)."""
        return self.group[next(iter(self.staging))].nblk if self.staging \
            else 0

    @property
    def stage_slots_free(self) -> int:
        """Staging slots currently on the free list (slots whose queued
        promotion has not drained are excluded — admission policy can
        pre-check capacity without forcing an early flush; slots parked
        above the adaptive ring limit are excluded too)."""
        return len(self._stage_free)

    @property
    def stage_limit(self) -> Optional[int]:
        """The adaptive staging-ring clamp (:meth:`set_stage_limit`):
        usable slots are ids ``< stage_limit``.  None = full capacity."""
        return self._stage_limit

    def set_stage_limit(self, limit: Optional[int]) -> int:
        """Clamp the staging ring to ``limit`` usable slots (ids below
        the limit); slots at or above it park until the limit is raised.

        The adaptive-ring primitive: the serving layer shrinks the ring
        under sustained low admission pressure (the occupancy gauge says
        most slots never fill) and regrows it on demand — in-flight and
        reserved slots are untouched either way, only FREE slots move
        between the usable and parked lists, so a shrink never invalidates
        outstanding reservations.  ``None`` (or ``limit >=``
        :attr:`stage_capacity`) restores the full ring.  A degraded
        ``recover()`` routes through here too.  Returns the effective
        usable-slot count."""
        cap = self.stage_capacity
        if limit is None or int(limit) >= cap:
            self._stage_limit = None
            self._stage_free.extend(self._stage_parked)
            self._stage_parked = []
            effective = cap
        else:
            lim = max(int(limit), 0)
            self._stage_limit = lim
            usable = [s for s in self._stage_free if s < lim] + \
                [s for s in self._stage_parked if s < lim]
            parked = [s for s in self._stage_free if s >= lim] + \
                [s for s in self._stage_parked if s >= lim]
            self._stage_free = usable
            self._stage_parked = parked
            effective = lim
        obs_metrics.set_gauge("engine.stage_limit", effective)
        return effective

    def _reclaim_stage_slots(self, slots: Sequence[int]) -> None:
        """Route freed staging slots to the free list, or to the parked
        list when the adaptive ring limit excludes their ids."""
        lim = self._stage_limit
        if lim is None:
            self._stage_free.extend(slots)
            return
        for s in slots:
            (self._stage_free if s < lim else self._stage_parked).append(s)

    @property
    def spill_capacity(self) -> int:
        """Demotion slots the engine owns (``enable_demotion``), per
        spill pool; 0 until demotion is enabled."""
        return len(self._spill_slots)

    @property
    def spill_slots_free(self) -> int:
        """Demotion slots not currently parking a demoted block and not
        awaiting reclaim from a queued resume promotion."""
        return len(self._spill_free)

    @property
    def n_primary(self) -> int:
        """Number of primary pools (plain opcodes touch exactly these;
        staging pools only see cross-pool commands)."""
        return self.group.n_primary

    @property
    def primary_names(self) -> Tuple[str, ...]:
        """Names of the primary pools, in table order."""
        return self.group.primary_names

    def _multi_device(self) -> bool:
        return self.mesh is not None and \
            int(np.prod(self.mesh.devices.shape)) > 1

    def _block_bytes(self) -> int:
        """Bytes one plain command moves = one block across every PRIMARY
        pool (staging pools never ride plain opcodes)."""
        total = 0
        for name in self.primary_names:
            p = self.pools[name]
            shape = list(p.shape)
            shape.pop(self.block_axis)
            total += int(np.prod(shape)) * p.dtype.itemsize
        return total

    def _pool_block_bytes(self, name: str) -> int:
        p = self.pools[name]
        shape = list(p.shape)
        shape.pop(self.block_axis)
        return int(np.prod(shape)) * p.dtype.itemsize

    def pool_bytes_resident(self) -> int:
        """Total bytes resident across every pool array (primary +
        staging).  The serving-memory headline number: sizing staging as a
        small ring instead of a full twin (per-pool ``nblk`` in the
        PoolGroup) roughly halves this for a k/v + staging engine —
        tracked per serve_round row in BENCH_dispatch.json (schema v4)."""
        return sum(int(np.prod(p.shape)) * p.dtype.itemsize
                   for p in self.pools.values())

    def _pad(self, pairs: Sequence[Tuple[int, int]]) -> np.ndarray:
        """Seed-style fixed-length padding (legacy fan-out path only)."""
        m = self.max_requests
        arr = np.full((m, 2), -1, np.int32)
        if pairs:
            a = np.asarray(pairs, np.int32)[:m]
            arr[: len(a)] = a
        return arr

    def _get_zero_blocks(self) -> Tuple[jnp.ndarray, ...]:
        """Per-pool reserved zero row for BuZ — allocated once."""
        if self._zero_blocks is None:
            zbs = []
            for p in self.pools.values():
                blk = p.shape[1:] if self.block_axis == 0 else p.shape[2:]
                zbs.append(jnp.zeros((1,) + blk, p.dtype))
            self._zero_blocks = tuple(zbs)
        return self._zero_blocks

    # ------------------------------------------------------------------
    # flush control
    # ------------------------------------------------------------------
    def flush(self) -> int:
        """Drain the DEFAULT stream's queue (seed-compatible surface).
        Returns device launches issued; other streams drain through their
        own ``flush()`` and return :class:`FlushTicket` receipts.  Always
        targets the default queue — even inside a ``stream.capture()``
        region, where captured commands stay queued until that stream's
        explicit flush (calling this mid-capture must not split the
        capturing stream's launch)."""
        return self._default_stream.queue.flush()

    def _flush_streams(self) -> None:
        """Drain EVERY queue with pending commands (the engine-wide
        barrier some internal paths need, e.g. staging-slot reclaim)."""
        for q in list(self._live_queues.values()):
            q.flush()

    def _autoflush(self) -> None:
        if not self.deferred:
            self._cur_queue.flush()

    @contextlib.contextmanager
    def batch(self) -> Iterator[CommandQueue]:
        """Defer flushing: commands enqueued inside the block drain as one
        fused launch at exit (the attention-step flush boundary).  Pool
        arrays are STALE inside the block — read them only after exit.
        Composes with stream capture: inside ``stream.capture()`` the
        commands land on that stream and its flush stays explicit."""
        prev = self.deferred
        self.deferred = True
        try:
            yield self._cur_queue
        finally:
            self.deferred = prev
            if not self.deferred:
                self._cur_queue.flush()

    # ------------------------------------------------------------------
    # drain path + journal — every flushed table passes through here
    # ------------------------------------------------------------------
    @property
    def next_flush_index(self) -> int:
        """Engine-wide index the NEXT drained flush will carry (every
        ``_drain_rows`` — flush, replay, or re-drain — takes one).  The
        handle fault plans use to target a specific upcoming flush."""
        return self._flush_index

    def _drain_rows(self, rows: Sequence[Tuple[int, int, int]],
                    queue: Optional[CommandQueue] = None,
                    record: bool = True, pre_spaced: bool = False) -> int:
        """Space, chunk, and dispatch one flush's rows; append the
        :class:`JournalRecord` on success.  The single drain path shared
        by ``CommandQueue.flush``, ``TicketJournal.replay``
        (``record=False, pre_spaced=True`` — records hold spaced rows),
        and ``recover()``'s aborted-suffix re-drains.

        Every chunk runs the drain guards (fused_dispatch ``check_drain``)
        BEFORE its donating dispatch, so a raising guard aborts the flush
        with pool buffers intact: the dispatched prefix is journaled as an
        ``aborted`` record and the undispatched suffix stashed for
        ``recover()``."""
        rows = [(int(op), int(s), int(d)) for op, s, d in rows]
        idx = self._flush_index
        self._flush_index += 1
        residency_us = queue.pop_residency_us() if queue is not None else 0.0
        t_drain = obs_metrics.now()
        if pre_spaced or not self._flush_spacing():
            spaced = rows
        else:
            spaced = space_war_rows(rows, self.group.locate,
                                    self.group.primary,
                                    self.group.total_blocks)
            if queue is not None:
                queue.stats.spacer_rows += len(spaced) - len(rows)
        self._last_plan_sig = None
        name = queue.name if queue is not None else "replay"
        launches = 0
        table_len = 0
        top = top_bucket()
        with span("drain", stream=name, flush=idx):
            for ci, lo in enumerate(range(0, len(spaced), top)):
                chunk = spaced[lo:lo + top]
                try:
                    kfd.check_drain(kfd.DrainInfo(
                        flush=idx, chunk=ci,
                        n_commands=sum(1 for r in chunk if r[0] >= 0),
                        n_pools=len(self.pools), engine=self))
                    table = np.full((bucket_size(len(chunk)), 3), OP_NOP,
                                    np.int32)
                    table[:len(chunk)] = np.asarray(chunk, np.int32)
                    table_len += len(table)
                    san = self.sanitizer
                    shadow_pre = None
                    if san is not None:
                        san.check_table(table, flush=idx, chunk=ci)
                        shadow_pre = san.shadow_snapshot()
                    launches += self._dispatch_table(table, len(chunk),
                                                     queue=queue)
                    if shadow_pre is not None:
                        san.check_shadow(shadow_pre, table)
                except Exception:
                    if record:
                        done = spaced[:lo]
                        if any(op >= 0 for op, _, _ in done):
                            # the chunks that DID dispatch mutated the
                            # pools: journal them so replay reproduces the
                            # partial state exactly (recover() re-drains
                            # the suffix as its own record)
                            self.journal.append(JournalRecord(
                                stream=name, index=idx, rows=tuple(done),
                                plan_sig=self._last_plan_sig,
                                launches=launches, aborted=True))
                        self._aborted.append(AbortedFlush(
                            queue=name, index=idx, rows=tuple(rows),
                            suffix=tuple(spaced[lo:])))
                    raise
        drain_us = (obs_metrics.now() - t_drain) * 1e6
        self.last_drain_timing = FlushTiming(
            queue_residency_us=residency_us, drain_us=drain_us,
            table_len=table_len, launches=launches)
        if obs_metrics.metrics_enabled():
            op_counts: Dict[int, int] = {}
            spacers = 0
            for op, _s, _d in spaced:
                if op < 0:
                    spacers += 1
                else:
                    op_counts[op] = op_counts.get(op, 0) + 1
            for op, cnt in op_counts.items():
                obs_metrics.inc("drain.rows", cnt, stream=name,
                                opcode=OPCODE_NAMES.get(op, str(op)))
            if spacers:
                obs_metrics.inc("drain.spacer_rows", spacers, stream=name)
            obs_metrics.inc("drain.launches", launches, stream=name)
            obs_metrics.observe("drain.flush_us", drain_us, stream=name)
            obs_metrics.observe("drain.table_len", table_len, stream=name)
        if record:
            self.journal.append(JournalRecord(
                stream=name, index=idx, rows=tuple(spaced),
                plan_sig=self._last_plan_sig, launches=launches,
                war_hazards=(queue.stats.war_hazards if queue else 0),
                spacer_rows=(queue.stats.spacer_rows if queue else 0)))
        return launches

    def _touched_pools(self, rows: Sequence[Tuple[int, int, int]]
                       ) -> Tuple[str, ...]:
        """Pool names a set of command rows WRITES — what a flush's
        :class:`FlushTicket` must wait on (plain opcodes write every
        primary pool; cross-pool rows write exactly their destination
        pool), so e.g. a checkpoint-stream ticket never serializes
        against decode's primary-pool traffic."""
        hit = set()
        for op, s, d in rows:
            if op < 0:
                continue
            _, writes = row_rw(op, s, d, self.group.locate,
                               self.group.total_blocks)
            for p, _b in writes:
                if p == ALL_PRIMARY:
                    hit.update(self.primary_names)
                else:
                    hit.add(self.group.names[p])
        return tuple(n for n in self.group.names if n in hit)

    # ------------------------------------------------------------------
    # snapshot + recovery
    # ------------------------------------------------------------------
    def snapshot(self) -> PoolSnapshot:
        """Host copies of EVERY pool, consistent through the last drained
        flush (quiesce in-flight streams first for an exact snapshot).
        The incremental, non-blocking alternative rides the checkpoint
        stream — checkpoint/pool_checkpoint.py."""
        return PoolSnapshot(
            index=self._flush_index - 1,
            arrays={n: np.asarray(p) for n, p in self.pools.items()})

    def _reads_lost(self, row: Tuple[int, int, int],
                    lost_idx: frozenset) -> bool:
        """Does a command row read (or write) a pool that died without a
        snapshot?  Such rows are unrecoverable — recover() drops them."""
        if not lost_idx:
            return False
        op, s, d = row
        # registry-driven decode: plain opcodes key ALL_PRIMARY (-1),
        # which is never a lost pool index, so only exact-pool operands
        # (cross-pool / bitwise rows) can make a row unrecoverable
        reads, writes = row_rw(op, s, d, self.group.locate,
                               self.group.total_blocks)
        return any(p in lost_idx for p, _b in reads + writes)

    def recover(self, snapshot: Optional[PoolSnapshot] = None,
                max_retries: int = 3, backoff: float = 0.05,
                degraded_stage_capacity: Optional[int] = None
                ) -> RecoveryReport:
        """Return the engine to a serviceable state after a failed flush
        or a donation error.  The recovery state machine:

        1. **Evict** — every live stream's queued commands are dropped
           (``CommandQueue.abort``); promotions out of the staging pools
           are counted separately so a serving layer can evict the
           affected admissions (their staged bytes never arrived).
        2. **Restore** — pools whose buffers died (donated into a failed
           call) come back from ``snapshot`` when it covers them, else as
           zeros (reported in ``pools_lost``).  Live pools are never
           touched: their bytes are ahead of any snapshot (decode writes
           bypass the journal) and must not be rolled back.
        3. **Reset staging** — all slots return to the free list (queued
           reads are gone); ``degraded_stage_capacity`` caps the ring
           (the degraded single-buffer mode when a shadow half is
           poisoned).
        4. **Replay** — when step 2 restored pools from the snapshot, the
           journal re-drains every record after ``snapshot.index``
           (bitwise-identical block state — core/journal.py).
        5. **Re-drain** — aborted flushes' undispatched suffixes re-drain
           with exponential backoff, up to ``max_retries`` attempts each;
           exhaustion raises :class:`RecoveryError`.  Rows reading pools
           lost without a snapshot are dropped (unrecoverable).
        """
        aborted, self._aborted = list(self._aborted), []
        evicted = 0
        evicted_promotions = 0
        staging_idx = frozenset(self.group.index(n) for n in self.staging)
        for q in list(self._live_queues.values()):
            for op, s, d in q.abort():
                if op < 0:
                    continue
                evicted += 1
                if op == OP_CROSS_POOL_COPY and \
                        self.group.locate(int(s))[0] in staging_idx:
                    evicted_promotions += 1
        restored: List[str] = []
        lost: List[str] = []
        for name in list(self.pools):
            p = self.pools[name]
            if not getattr(p, "is_deleted", lambda: False)():
                continue
            shape, dtype, sh = self._pool_layouts[name]
            if snapshot is not None and name in snapshot.arrays:
                arr = jnp.asarray(np.asarray(snapshot.arrays[name]),
                                  dtype=dtype)
                restored.append(name)
            else:
                arr = jnp.zeros(shape, dtype)
                lost.append(name)
            if sh is not None:
                arr = jax.device_put(arr, sh)
            self.pools[name] = arr
        # staging: every reservation and queued promotion is void now
        self._stage_inflight = []
        # in-flight resume promotions were aborted with the queues; their
        # slots revert to whoever demoted them (the serving layer either
        # re-promotes or releases via its demoted-sequence registry)
        self._spill_inflight = []
        cap = self.stage_capacity
        self._stage_free = list(range(cap - 1, -1, -1))
        self._stage_parked = []
        self._stage_limit = None
        if degraded_stage_capacity is not None:
            self._stage_degraded_cap = min(cap, int(degraded_stage_capacity))
            self.set_stage_limit(self._stage_degraded_cap)
        else:
            self._stage_degraded_cap = None
        replayed = 0
        if restored and snapshot is not None:
            replayed = self.journal.replay(self, after=snapshot.index)
        retries = 0
        lost_idx = frozenset(self.group.index(n) for n in lost)
        redrained = 0
        for ab in aborted:
            rows = [r for r in ab.suffix
                    if not self._reads_lost(r, lost_idx)]
            if not any(op >= 0 for op, _, _ in rows):
                continue
            for attempt in range(max_retries):
                try:
                    self._drain_rows(rows, record=True, pre_spaced=True)
                    redrained += 1
                    break
                except Exception as e:
                    self._aborted = []  # failed retries don't re-stash
                    retries += 1
                    if attempt == max_retries - 1:
                        raise RecoveryError(
                            f"re-drain of flush {ab.index} (stream "
                            f"{ab.queue!r}) still failing after "
                            f"{max_retries} attempts") from e
                    time.sleep(backoff * (2 ** attempt))
        return RecoveryReport(
            evicted_rows=evicted, evicted_promotions=evicted_promotions,
            pools_restored=tuple(restored), pools_lost=tuple(lost),
            replayed_flushes=replayed, redrained_flushes=redrained,
            retries=retries,
            degraded=degraded_stage_capacity is not None)

    # ------------------------------------------------------------------
    # memcopy
    # ------------------------------------------------------------------
    def _primary_id(self, b) -> int:
        """Resolve a primary-address-space operand: a bare int is an
        allocator block id; a :class:`BlockRef` must name a primary pool
        (plain opcodes move the block in EVERY primary pool, so the ref's
        pool only validates intent — the id is the address)."""
        if isinstance(b, BlockRef):
            if b.pool not in self.group.primary_names:
                raise ValueError(
                    f"plain copy/init addresses primary pools; "
                    f"{b.pool!r} is a staging pool (use memcopy_cross)")
            if not 0 <= int(b.block) < self.num_blocks:
                raise ValueError(f"block {b.block} out of range for "
                                 f"primary pools ({self.num_blocks})")
            return int(b.block)
        return int(b)

    def memcopy(self, pairs: Sequence[Tuple[object, object]],
                dst_is_fresh: bool = False) -> Dict[str, int]:
        """Copy block src -> dst for each pair.  Returns dispatch counts.

        Pairs may be bare ints (allocator block ids) or
        :class:`BlockRef`\\ s naming a primary pool — either way the copy
        moves the block in every primary pool (K and V pages travel
        together).

        ``dst_is_fresh``: destinations have never been written (e.g. CoW
        targets) — with ZI the engine may satisfy zero-source copies by
        aliasing at the cache layer instead; that path lives in
        cow_cache.fork() and never reaches here.
        """
        counts = {"fpm": 0, "psm": 0, "baseline": 0}
        bb = self._block_bytes()
        aliased = 0
        for s, d in pairs:
            s, d = self._primary_id(s), self._primary_id(d)
            # ZI "in-cache copy" fast path: copying a lazily-zero block is a
            # metadata move — mark dst zero, move no bytes.
            if self.enable_zi and self.alloc.is_zero[s]:
                self.alloc.mark_zero([d])
                self.stats.alias_copies += 1
                self.stats.bytes_avoided += bb
                aliased += 1
                continue
            # mark the dst written NOW, not after the loop: a later pair in
            # this same call may read it as a source (chained (a,b),(b,c))
            # and must see it as real data, not stale lazy-zero metadata
            self.alloc.mark_written([d])
            if not self.enable_fpm:
                op = OP_BASELINE_COPY
            elif self.alloc.slab_of(s) == self.alloc.slab_of(d):
                op = OP_FPM_COPY
            elif self.enable_psm:
                op = OP_PSM_COPY
            else:
                op = OP_BASELINE_COPY
            if op == OP_FPM_COPY:
                counts["fpm"] += 1
                self.stats.fpm_copies += 1
                self.stats.bytes_fpm += bb
            elif op == OP_PSM_COPY:
                counts["psm"] += 1
                self.stats.psm_copies += 1
                self.stats.bytes_psm += bb
            else:
                counts["baseline"] += 1
                self.stats.baseline_copies += 1
                self.stats.bytes_baseline += bb
            self._cur_queue.enqueue(op, s, d)
        if obs_metrics.metrics_enabled():
            for mech, c in counts.items():
                if c:
                    obs_metrics.inc("engine.bytes_moved", c * bb,
                                    mechanism=mech)
            if aliased:
                obs_metrics.inc("engine.bytes_avoided", aliased * bb,
                                mechanism="alias")
        self._autoflush()
        return counts

    def memcopy_cross(self, pairs: Sequence[Tuple[object, object]]) -> int:
        """Pool-to-pool block copy (e.g. prefill staging pool → serving
        pool) through the same queue: each pair becomes one
        ``CROSS_POOL_COPY`` command carrying global ``base[pool] + block``
        ids from the engine's :class:`PoolGroup`, so it rides the same
        fused launch as any pending copies/inits — and pools of DIFFERENT
        sizes (a staging ring vs a full KV pool) coexist in one table.
        Source and destination pools must share block shape and dtype.

        ``pairs`` are ``(BlockRef, BlockRef)`` — each pair names its own
        pools, so one call may mix pool pairs.  (The pre-stream
        ``(pairs, src_pool, dst_pool)`` int form is gone.)

        Staging and spill pools sit outside the allocator's metadata: a
        staging *source* always holds real bytes (the prefill wrote
        them), so the lazy-zero materialization below is skipped; a
        staging or spill *destination* is an engine- (or checkpoint-)
        managed slot, so no allocator block is marked written."""
        pairs = [(s if isinstance(s, BlockRef) else None,
                  d if isinstance(d, BlockRef) else None)
                 for s, d in pairs]
        if any(s is None or d is None for s, d in pairs):
            raise TypeError(
                "memcopy_cross pairs must be (BlockRef, BlockRef)")
        # validate every ref up front: the lazy-zero scan below indexes
        # allocator metadata, and a bad block id must fail cleanly before
        # any command or materialization side effect
        for s, d in pairs:
            self.group.gid(s), self.group.gid(d)
        # a lazily-zero PRIMARY source physically holds stale bytes; the ZI
        # bit is per *block* (primary pools jointly), so materialize it
        # before the pool-level copy (the hazard guard orders the zero
        # before the copy)
        lazy_srcs = [int(s.block) for s, _ in pairs
                     if s.pool in self.primary_names
                     and self.enable_zi and self.alloc.is_zero[s.block]]
        if lazy_srcs:
            self.materialize_zeros(lazy_srcs)
        for s, d in pairs:
            self._cur_queue.enqueue(OP_CROSS_POOL_COPY, self.group.gid(s),
                                    self.group.gid(d))
            self.stats.cross_pool_copies += 1
            self.stats.bytes_cross += self._pool_block_bytes(d.pool)
            obs_metrics.inc("engine.bytes_moved",
                            self._pool_block_bytes(d.pool),
                            mechanism="cross", pool=d.pool)
            if d.pool in self.primary_names:
                # dst now holds real data in dst_pool; a block can only
                # carry the lazy-zero bit when every primary pool's bytes
                # are logically zero.  Staging and spill destinations are
                # outside the allocator's metadata — a checkpoint copy
                # into a spill pool must NOT mark the primary block.
                self.alloc.mark_written([int(d.block)])
        self._autoflush()
        return len(pairs)

    # ------------------------------------------------------------------
    # bitwise compute rows — in-memory AND/OR/NOT (Ambit triple-row
    # activation analogue) through the same queue and fused launch
    # ------------------------------------------------------------------
    def _bitwise_rows(self, triples, verb: str):
        """Normalize ``(a, b, dst)`` operand triples to global-id rows.

        Each triple is either all :class:`BlockRef`\\ s (any pool,
        matching block shape/dtype assumed group-wide) or all bare ints
        (primary-space ids — the op fans out to every primary pool, the
        plain-opcode convention).  Lazily-zero PRIMARY sources hold stale
        bytes, so they materialize first, exactly like ``memcopy_cross``
        sources."""
        rows = []
        lazy = set()
        for t in triples:
            a, b, d = t
            refs = [isinstance(x, BlockRef) for x in (a, b, d)]
            if any(refs):
                if not all(refs):
                    raise TypeError(
                        f"{verb}: each triple must be all BlockRefs or "
                        f"all ints, got {t!r}")
                for x in (a, b):
                    if x.pool in self.primary_names and self.enable_zi \
                            and self.alloc.is_zero[int(x.block)]:
                        lazy.add(int(x.block))
                rows.append((self.group.gid(a), self.group.gid(b),
                             self.group.gid(d), d))
            else:
                ai = self._primary_id(a)
                bi = self._primary_id(b)
                di = self._primary_id(d)
                for x in (ai, bi):
                    if self.enable_zi and self.alloc.is_zero[x]:
                        lazy.add(x)
                for pname in self.primary_names:
                    base = self.group.base(pname)
                    rows.append((base + ai, base + bi, base + di,
                                 BlockRef(pname, di)))
        if lazy:
            # the RAW guard orders the zero-init ahead of the compute row
            self.materialize_zeros(sorted(lazy))
        return rows

    def _membitwise(self, op: int, rows) -> int:
        total = self.group.total_blocks
        # registry-enforced int32 bound — the same check runs on every
        # pack/unpack (enqueue, retire, journal replay), not just here
        check_pack_total(total)
        for a, b, d, dref in rows:
            self._cur_queue.enqueue(op, pack_bitwise_src(a, b, total), d)
            self.stats.bitwise_ops += 1
            self.stats.bytes_bitwise += self._pool_block_bytes(dref.pool)
            obs_metrics.inc("engine.bytes_moved",
                            self._pool_block_bytes(dref.pool),
                            mechanism="bitwise", pool=dref.pool)
            if dref.pool in self.primary_names:
                # dst now holds computed (generally non-zero) bytes
                self.alloc.mark_written([int(dref.block)])
        self._autoflush()
        return len(rows)

    def memand(self, triples) -> int:
        """Bitwise AND: ``dst = a & b`` block-wise for each ``(a, b,
        dst)`` triple, over the raw bit patterns (float pools combine via
        a same-width unsigned bitcast).  Triples are all-BlockRef (any
        pool mix, including staging) or all-int (primary space, fanned
        out to every primary pool).  ``dst`` may equal either source.
        Rides the current stream's queue like any copy — two-source
        hazards (RAW/WAW on either source) auto-flush, WAR is spaced."""
        return self._membitwise(OP_AND, self._bitwise_rows(triples,
                                                           "memand"))

    def memor(self, triples) -> int:
        """Bitwise OR: ``dst = a | b`` block-wise for each ``(a, b,
        dst)`` triple — same addressing, hazard, and bitcast semantics as
        :meth:`memand`."""
        return self._membitwise(OP_OR, self._bitwise_rows(triples,
                                                          "memor"))

    def memnot(self, pairs) -> int:
        """Bitwise NOT: ``dst = ~src`` block-wise for each ``(src,
        dst)`` pair (the packed second source repeats ``src``) — same
        addressing, hazard, and bitcast semantics as :meth:`memand`."""
        return self._membitwise(
            OP_NOT, self._bitwise_rows([(s, s, d) for s, d in pairs],
                                       "memnot"))

    # ------------------------------------------------------------------
    # staging — prefill pages park in a staging pool, then promote into
    # allocator-owned primary blocks through the SAME command queue
    # ------------------------------------------------------------------
    def stage_blocks(self, n: int) -> List[int]:
        """Reserve ``n`` staging slot ids for an incoming prefill write.

        Slot ids index the staging pools' OWN address space
        (``stage_capacity`` slots — a staging ring may be far smaller than
        the KV pools).  Slots with a pending READ on any stream are not
        reused (a queued ``CROSS_POOL_COPY`` promotion must see the bytes
        currently parked there — the queues' source-hazard tracking is
        the ground truth); when the free list runs short the engine
        drains every stream first, which reclaims the in-flight slots."""
        if not self.staging:
            raise RuntimeError("engine has no staging pools")
        if len(self._stage_free) < n:
            self._flush_streams()  # drains promotions -> reclaims inflight
        if len(self._stage_free) < n:
            raise RuntimeError(
                f"staging pool exhausted ({n} slots requested, "
                f"{len(self._stage_free)} free of {self.stage_capacity})")
        return [self._stage_free.pop() for _ in range(n)]

    def release_stage_blocks(self, ids: Sequence[int]) -> None:
        """Return reserved staging slots that were never promoted (e.g. an
        admission that failed after ``stage_blocks``)."""
        self._reclaim_stage_slots([int(b) for b in ids])

    def promote_staged(self, pairs: Sequence[Tuple[int, object]]) -> int:
        """Promote staged prefill pages into primary pool blocks.

        ``pairs``: (staging_slot, dst) — the slot is a ``stage_blocks``
        id; the destination is a primary block id (int) or a
        :class:`BlockRef` into a primary pool.  Every registered staging
        pool promotes into its paired primary pool (k_stage→k and
        v_stage→v move in the same table), one ``CROSS_POOL_COPY`` command
        per pool pair per block — with pool-aware hazard keys, the whole
        promotion plus the round's CoW splits and tail inits drain as ONE
        fused launch at the next flush boundary.  Staging slots are
        reclaimed automatically once the queue drains."""
        if not self.staging:
            raise RuntimeError("engine has no staging pools")
        pairs = [(int(s), self._primary_id(d)) for s, d in pairs]
        with self.batch():
            for sname, pname in self.staging.items():
                self.memcopy_cross([(BlockRef(sname, s), BlockRef(pname, d))
                                    for s, d in pairs])
            # inside the batch: slots must be in-flight BEFORE the exit
            # flush so _after_flush reclaims them with that drain
            self.stats.stage_promotions += len(pairs)
            self._stage_inflight.extend(s for s, _ in pairs)
        return len(pairs)

    def retire_promotions(self, pairs: Sequence[Tuple[int, object]]) -> int:
        """Cancel queued stage→primary promotions and recycle their slots.

        ``pairs`` mirrors :meth:`promote_staged`: (staging_slot, dst).
        The sequence-lifecycle primitive behind ``ServingEngine.free``: a
        sequence freed *before* the round's flush returns its blocks to
        the allocator while its promotions still sit on a stream — left
        queued, they would drain later and overwrite whatever the
        allocator re-issued those blocks for.  Every matching pending row
        is removed from every live queue
        (:meth:`~repro.core.cmdqueue.CommandQueue.retire`); promotions
        that already drained are simply not found (their bytes landed
        before the free — harmless, the blocks were still owned then).
        Slots whose pending reads disappeared return to the free list.
        Returns the number of command rows retired."""
        if not self.staging:
            return 0
        pairs = [(int(s), self._primary_id(d)) for s, d in pairs]
        rows = [(OP_CROSS_POOL_COPY,
                 self.group.base(sname) + s, self.group.base(pname) + d)
                for sname, pname in self.staging.items()
                for s, d in pairs]
        removed = 0
        for q in list(self._live_queues.values()):
            removed += q.retire(rows)
        self.stats.retired_promotions += removed
        # slots freed of their pending reads rejoin the ring now
        self._after_flush()
        return removed

    # ------------------------------------------------------------------
    # demotion — preemption parks primary blocks in spill slots (the
    # reverse of promotion), resumption promotes them back
    # ------------------------------------------------------------------
    def enable_demotion(self, slots: Sequence[int]) -> None:
        """Hand the engine a set of spill-pool slot ids for preemption.

        ``slots`` index the spill pools' own address space and become the
        engine-owned demotion slot space (:meth:`demote_to_spill` draws
        from it; resumed or released slots return to it).  Callers that
        also run windowed checkpoints over the same spill pools give the
        two users disjoint ranges — the serving engine reserves
        ``[ckpt_window, ckpt_window + spill_pages)`` for demotion."""
        if not self._spill_map:
            raise RuntimeError(
                "engine has no spill pools (PoolSpec(role='spill')); "
                "serving builds them via make_serving_pools")
        cap = min(self.group[n].nblk for n in self._spill_map.values())
        slots = [int(s) for s in slots]
        for s in slots:
            if not 0 <= s < cap:
                raise ValueError(f"spill slot {s} out of range ({cap})")
        self._spill_slots = tuple(slots)
        self._spill_free = list(reversed(slots))
        self._spill_inflight = []

    def demote_to_spill(self, blocks: Sequence[object]) -> List[int]:
        """Evict primary blocks into spill slots — preemption by demotion.

        The reverse of :meth:`promote_staged`: each block cross-pool-
        copies into one demotion slot per spill pool pair (k→k_spill and
        v→v_spill travel together), riding the current queue like any
        bulk movement — a whole preemption adds rows to the round's one
        fused launch.  Returns the slot ids parking each block's bytes,
        in block order; the caller owns them until
        :meth:`promote_spilled` (resumption) or
        :meth:`release_spill_slots` (the demoted sequence died).

        The copy reads the blocks' CURRENT pool bytes.  Callers whose
        pools are written out of band of the allocator's ZI metadata
        (e.g. decode steps appending tokens in-jit) must
        ``alloc.mark_written`` the blocks first, or a stale lazy-zero bit
        would materialize zeros over the real bytes."""
        if not self._spill_slots:
            raise RuntimeError("demotion not enabled (enable_demotion)")
        blocks = [self._primary_id(b) for b in blocks]
        if len(self._spill_free) < len(blocks):
            raise RuntimeError(
                f"spill slots exhausted ({len(blocks)} requested, "
                f"{len(self._spill_free)} free of {self.spill_capacity})")
        slots = [self._spill_free.pop() for _ in blocks]
        with self.batch():
            for pname, sname in self._spill_map.items():
                self.memcopy_cross(
                    [(BlockRef(pname, b), BlockRef(sname, s))
                     for b, s in zip(blocks, slots)])
            self.stats.demotions += len(blocks)
        return slots

    def promote_spilled(self, pairs: Sequence[Tuple[int, object]]) -> int:
        """Promote demoted bytes back into primary blocks — resumption.

        ``pairs``: (spill_slot, dst primary block).  Mirrors
        :meth:`promote_staged` with the spill pools as the source; the
        slots join the in-flight list and return to the demotion free
        list once no stream holds a pending read of them (the same
        source-hazard lifetime as staging slots)."""
        if not self._spill_slots:
            raise RuntimeError("demotion not enabled (enable_demotion)")
        pairs = [(int(s), self._primary_id(d)) for s, d in pairs]
        with self.batch():
            for pname, sname in self._spill_map.items():
                self.memcopy_cross(
                    [(BlockRef(sname, s), BlockRef(pname, d))
                     for s, d in pairs])
            self.stats.spill_promotions += len(pairs)
            self._spill_inflight.extend(s for s, _ in pairs)
        return len(pairs)

    def release_spill_slots(self, ids: Sequence[int]) -> None:
        """Return demotion slots whose parked bytes are no longer needed
        (the demoted sequence finished, was cancelled, or was evicted by
        a recovery) without promoting them back.  Idempotent: slots
        already free (or still in flight — a resume promotion that
        drained reclaims through ``_after_flush``) are skipped, so
        recovery paths can release conservatively."""
        for s in ids:
            s = int(s)
            if s not in self._spill_free and s not in self._spill_inflight:
                self._spill_free.append(s)

    def _after_flush(self, queue: Optional[CommandQueue] = None) -> None:
        """CommandQueue callback after any stream drains: a staging (or
        in-flight demotion) slot is reusable exactly when NO stream still
        holds a pending read of it (the source-hazard tracking) —
        promotions that drained free their slots, promotions still queued
        on another stream keep theirs."""
        if self._stage_inflight:
            sidx = [self.group.index(name) for name in self.staging]
            queues = list(self._live_queues.values())
            still: List[int] = []
            freed: List[int] = []
            for slot in self._stage_inflight:
                if any(q.has_pending_read((p, slot))
                       for q in queues for p in sidx):
                    still.append(slot)
                else:
                    freed.append(slot)
            self._reclaim_stage_slots(freed)
            self._stage_inflight = still
        if self._spill_inflight:
            pidx = [self.group.index(name)
                    for name in self._spill_map.values()]
            queues = list(self._live_queues.values())
            still = []
            freed = []
            for slot in self._spill_inflight:
                if any(q.has_pending_read((p, slot))
                       for q in queues for p in pidx):
                    still.append(slot)
                else:
                    freed.append(slot)
            self._spill_free.extend(freed)
            self._spill_inflight = still

    # ------------------------------------------------------------------
    # meminit
    # ------------------------------------------------------------------
    def meminit(self, ids: Sequence[object],
                lazy: Optional[bool] = None) -> int:
        """Zero blocks (ints or primary-pool :class:`BlockRef`\\ s).
        Returns number physically zeroed (0 with ZI)."""
        ids = [self._primary_id(b) for b in ids]
        if lazy is None:
            lazy = self.enable_zi
        if lazy:
            self.alloc.mark_zero(ids)
            self.stats.zero_lazy += len(ids)
            self.stats.bytes_avoided += len(ids) * self._block_bytes()
            obs_metrics.inc("engine.bytes_avoided",
                            len(ids) * self._block_bytes(),
                            mechanism="zero_lazy")
            return 0
        self.materialize_zeros(ids)
        return len(ids)

    def materialize_zeros(self, ids: Sequence[object]) -> None:
        """BuZ through the reserved zero row (FPM copy from zero block).
        ``ids`` are ints or primary-pool :class:`BlockRef`\\ s."""
        ids = [self._primary_id(b) for b in ids]
        if not ids:
            return
        self.stats.zero_materialized += len(ids)
        self._cur_queue.enqueue_zero(ids)
        self.alloc.mark_written(ids)  # physically zero: ordinary data now
        self._autoflush()

    # ------------------------------------------------------------------
    # dispatch — called by CommandQueue.flush with a bucket-padded table
    # ------------------------------------------------------------------
    def _flush_spacing(self) -> bool:
        """Should CommandQueue.flush WAR-space the global table?  Yes for
        every single-device drain (the fused kernel consumes the spacing;
        the legacy fan-out ignores NOP rows, keeping A/B stats aligned).
        No when the flush will be mesh-partitioned: _dispatch_sharded
        strips global NOPs and partition_commands re-spaces each slab
        sub-table, so global spacers would only eat 512-row chunk budget
        (risking an extra collective launch) for nothing."""
        return not (self.use_fused and self._multi_device()
                    and pool_shard_count(self.mesh) > 1)

    def _pool_replicated(self) -> Tuple[bool, ...]:
        """Per-pool replication vector from the ``PoolSpec.sharding``
        hints: ``()`` marks a pool held whole on every device (a small
        staging ring) — its block axis never partitions in the sharded
        drain."""
        return tuple(s.sharding == () for s in self.group)

    def _dispatch_table(self, table: np.ndarray, n_cmds: int,
                        queue: Optional[CommandQueue] = None) -> int:
        """Execute one flushed command table.  Returns launches issued.
        ``queue`` (the flushing CommandQueue, when called from a flush)
        receives accounting the dispatch path itself produces — e.g. the
        per-slab WAR spacers the mesh partitioner inserts."""
        if not int((np.asarray(table)[:, 0] >= 0).sum()):
            return 0        # all-NOP/empty table: no launch on ANY path
        if self.use_fused:
            n_shards = pool_shard_count(self.mesh)
            if self._multi_device() and n_shards > 1:
                replicated = self._pool_replicated()
                ragged = [s.name for i, s in enumerate(self.group)
                          if not replicated[i] and s.nblk % n_shards]
                if ragged:
                    # can't partition: slabs would be ragged.  Degrade to
                    # the fan-out, but loudly — the caller loses the
                    # one-launch-per-flush invariant (serving rounds every
                    # pool's nblk to the shard count exactly to avoid
                    # this).
                    if not self._warned_unshardable:
                        self._warned_unshardable = True
                        warnings.warn(
                            f"RowCloneEngine: pools {ragged} have block "
                            f"counts not divisible by {n_shards} device "
                            "shards; mesh flushes fall back to the "
                            "multi-launch legacy fan-out")
                    return self._dispatch_legacy(table)
                if any(replicated) and self._writes_replicated(table,
                                                               replicated):
                    # a sharded→replicated cross write needs a broadcast
                    # hop the collective drain doesn't model; GSPMD's
                    # global gather/scatter handles it on the fan-out
                    return self._dispatch_legacy(table)
                return self._dispatch_sharded(table, n_shards, replicated,
                                              queue)
            if not self._multi_device():
                pools = tuple(self.pools.values())
                new = kops.fused_dispatch(pools, self._get_zero_blocks(),
                                          jnp.asarray(table),
                                          block_axis=self.block_axis,
                                          primary=self.group.primary,
                                          overlap=self.overlap)
                for name, arr in zip(self.pools, new):
                    self.pools[name] = arr
                self.stats.launches += 1
                return 1
        return self._dispatch_legacy(table)

    def _writes_replicated(self, table: np.ndarray,
                           replicated: Tuple[bool, ...]) -> bool:
        """Does any cross-pool row write a replicated pool from a SHARDED
        source?  (Replicated→replicated writes drain collectively — every
        shard applies them to its replica.)"""
        for op, s, d in table:
            op = int(op)
            # only global-dst rows (cross-pool / bitwise, per the
            # registry) can write a replicated pool from a sharded source
            if op < 0 or opspec(op).dst_kind != "global":
                continue
            reads, writes = row_rw(op, int(s), int(d), self.group.locate,
                                   self.group.total_blocks)
            pd = writes[0][0]
            if replicated[pd] and any(not replicated[p]
                                      for p, _b in reads):
                return True
        return False

    def _dispatch_sharded(self, table: np.ndarray, n_shards: int,
                          replicated: Tuple[bool, ...],
                          queue: Optional[CommandQueue] = None) -> int:
        """One collective launch for the whole table: per-slab sub-tables
        (slab-local ids, each pool partitioned by its OWN shard size;
        replicated pools ride whole on every shard) drain inside
        shard_map, cross-slab commands ride the same launch as a ppermute
        send/recv plan.  The partitioner's per-slab WAR spacers are
        credited to the flushing ``queue``'s stats (global spacing is
        skipped on this path — _flush_spacing)."""
        rows = [(int(op), int(s), int(d)) for op, s, d in table if op >= 0]
        plan = partition_commands(rows, n_shards=n_shards, group=self.group,
                                  replicated=replicated)
        if self.sanitizer is not None:
            self.sanitizer.check_plan(rows, plan, replicated)
        # journal the plan shape (not the tables — rows reproduce those):
        # a replayed drain rebuilding a different signature would compile
        # a new collective, which the plan_sig makes observable
        self._last_plan_sig = (plan.n_shards, plan.deltas,
                               int(plan.send_rows.shape[2]))
        if queue is not None:
            queue.stats.spacer_rows += plan.n_spacers
        new = kops.fused_dispatch_sharded(
            tuple(self.pools.values()), self._get_zero_blocks(), plan,
            mesh=self.mesh, pool_axes=pool_shard_axes(self.mesh),
            block_axis=self.block_axis, primary=self.group.primary,
            replicated=replicated)
        for name, arr in zip(self.pools, new):
            self.pools[name] = arr
        self.stats.launches += 1
        return 1

    def _dispatch_legacy(self, table: np.ndarray) -> int:
        """Seed-shaped fan-out: one device call per mechanism per pool,
        padded to ``max_requests``.  Kept for A/B benchmarking
        (``use_fused=False``); on sharded pools the global gather/scatters
        compile through GSPMD — the mesh fast path is _dispatch_sharded.

        Commands are batched per *consecutive run* of one opcode, in
        enqueue order — NOT grouped across the whole table.  The hazard
        guard permits write-after-read (a later command overwriting an
        earlier command's source); whole-table grouping would reorder
        those and diverge from the fused drain.  Within one run the
        gather-then-scatter helpers read pre-run state, which the RAW/WAW
        guards make equal to in-order semantics."""
        rows = [(int(op), int(s), int(d)) for op, s, d in table if op >= 0]
        launches = 0
        i = 0
        while i < len(rows):
            op = rows[i][0]
            j = i
            while j < len(rows) and rows[j][0] == op:
                j += 1
            run = [(s, d) for _, s, d in rows[i:j]]
            if op == OP_FPM_COPY:
                launches += self._legacy_fpm(run)
            elif op == OP_PSM_COPY:
                launches += self._legacy_psm(run)
            elif op == OP_BASELINE_COPY:
                launches += self._legacy_baseline(run)
            elif op == OP_ZERO_INIT:
                launches += self._legacy_zero([d for _, d in run])
            elif op == OP_CROSS_POOL_COPY:
                launches += self._legacy_cross(run)
            elif op in BITWISE_OPS:
                launches += self._legacy_bitwise(op, run)
            i = j
        self.stats.launches += launches
        return launches

    # -- legacy per-mechanism fan-out (seed A/B path) --------------------
    def _legacy_use_pallas(self) -> Optional[bool]:
        """Impl override for the legacy fan-out's block_axis=0 ops: under a
        mesh, force the jnp reference — a pallas_call has no SPMD
        partitioning rule, so only the plain gather/scatter compiles
        through GSPMD on sharded pools.  ``None`` = the standard
        resolution (Pallas on TPU) everywhere else."""
        return False if self._multi_device() else None

    def _legacy_fpm(self, pairs: List[Tuple[int, int]]) -> int:
        """Same-slab copies, one global gather/scatter per pool.  On
        sharded pools the reference op compiles through GSPMD (the seed's
        hand-rolled per-slab shard_map fan-out — and its per-slab overflow
        table — is retired; the mesh fast path is ``_dispatch_sharded``)."""
        launches = 0
        for chunk in _chunks(pairs, self.max_requests):
            ids = jnp.asarray(self._pad(chunk))
            for name in self.primary_names:
                if self.block_axis == 1:
                    self.pools[name] = _fpm_axis1_jit(self.pools[name],
                                                      ids)
                else:
                    self.pools[name] = kops.fpm_copy(
                        self.pools[name], ids,
                        use_pallas=self._legacy_use_pallas())
                kfd.notify_launch(self.max_requests, 1, "legacy_fpm")
                launches += 1
        return launches

    def _legacy_psm(self, pairs: List[Tuple[int, int]]) -> int:
        """Cross-slab transfer over the interconnect (DRAM internal bus →
        ICI).  Expressed as a global gather/scatter; XLA lowers the
        cross-shard movement to collective-permutes — the pipelined serial
        path — without any host round-trip."""
        launches = 0
        fn = _fpm_axis1_jit if self.block_axis == 1 else _psm_jit
        for chunk in _chunks(pairs, self.max_requests):
            ids = jnp.asarray(self._pad(chunk))
            for name in self.primary_names:
                self.pools[name] = fn(self.pools[name], ids)
                kfd.notify_launch(self.max_requests, 1, "legacy_psm")
                launches += 1
        return launches

    def _legacy_baseline(self, pairs: List[Tuple[int, int]]) -> int:
        launches = 0
        for chunk in _chunks(pairs, self.max_requests):
            ids = jnp.asarray(self._pad(chunk))
            for name in self.primary_names:
                if self.block_axis == 1:
                    self.pools[name] = _baseline_axis1_jit(self.pools[name],
                                                           ids)
                else:
                    self.pools[name] = kops.baseline_copy(self.pools[name],
                                                          ids)
                kfd.notify_launch(self.max_requests, 1, "legacy_baseline")
                launches += 1
        return launches

    def _legacy_zero(self, ids_list: List[int]) -> int:
        launches = 0
        m = self.max_requests
        for chunk in _chunks(ids_list, m):
            arr = np.full((m,), -1, np.int32)
            arr[: len(chunk)] = np.asarray(chunk, np.int32)
            idv = jnp.asarray(arr)
            for name in self.primary_names:
                pool = self.pools[name]
                if self.block_axis == 1:
                    self.pools[name] = _zero_axis1_jit(pool, idv)
                else:
                    zero_block = jnp.zeros((1,) + pool.shape[1:], pool.dtype)
                    self.pools[name] = kops.meminit_zero(
                        pool, zero_block, idv,
                        use_pallas=self._legacy_use_pallas())
                kfd.notify_launch(self.max_requests, 1, "legacy_zero")
                launches += 1
        return launches

    def _legacy_cross(self, stacked_pairs: List[Tuple[int, int]]) -> int:
        """Pool-pair sub-runs execute in ENQUEUE order, not grouped across
        the whole run: interleaved opposite-direction copies (k->v, v->k,
        k->v) may carry a write-after-read the hazard guard permits —
        whole-table grouping would reorder the later write ahead of the
        earlier read and diverge from the fused drain.  Global ids decode
        through the PoolGroup's prefix-sum bases (pools may differ in
        size)."""
        launches = 0
        names = list(self.pools)
        locate = self.group.locate
        loc = [(locate(s), locate(d)) for s, d in stacked_pairs]
        i = 0
        while i < len(stacked_pairs):
            key = (loc[i][0][0], loc[i][1][0])
            run: List[Tuple[int, int]] = []
            j = i
            while j < len(stacked_pairs) and \
                    (loc[j][0][0], loc[j][1][0]) == key:
                run.append((loc[j][0][1], loc[j][1][1]))
                j += 1
            ps, pd = key
            for chunk in _chunks(run, self.max_requests):
                ids = jnp.asarray(self._pad(chunk))
                if self.block_axis == 1:
                    self.pools[names[pd]] = _cross_axis1_jit(
                        self.pools[names[pd]], self.pools[names[ps]], ids)
                else:
                    self.pools[names[pd]] = kops.fpm_copy_cross(
                        self.pools[names[pd]], self.pools[names[ps]], ids,
                        use_pallas=self._legacy_use_pallas())
                kfd.notify_launch(self.max_requests, 1, "legacy_cross")
                launches += 1
            i = j
        return launches

    def _legacy_bitwise(self, op: int,
                        stacked_pairs: List[Tuple[int, int]]) -> int:
        """Bitwise compute rows on the fan-out path: pool-triple sub-runs
        execute in ENQUEUE order (same WAR-preserving discipline as
        ``_legacy_cross``), each as one gather-both-sources /
        bitcast-combine / scatter device call.  The packed ``srcB``
        decodes with the group's ``total_blocks``."""
        launches = 0
        names = list(self.pools)
        locate = self.group.locate
        total = self.group.total_blocks
        dec = []
        for s, d in stacked_pairs:
            a, b = unpack_bitwise_src(s, total)
            dec.append((locate(a), locate(b), locate(d)))
        i = 0
        while i < len(stacked_pairs):
            key = (dec[i][0][0], dec[i][1][0], dec[i][2][0])
            run: List[Tuple[int, int, int]] = []
            j = i
            while j < len(stacked_pairs) and \
                    (dec[j][0][0], dec[j][1][0], dec[j][2][0]) == key:
                run.append((dec[j][0][1], dec[j][1][1], dec[j][2][1]))
                j += 1
            pa, pb, pd = key
            m = self.max_requests
            for chunk in _chunks(run, m):
                arr = np.full((m, 3), -1, np.int32)
                arr[:len(chunk)] = np.asarray(chunk, np.int32)
                self.pools[names[pd]] = _bitwise_jit(
                    self.pools[names[pd]], self.pools[names[pa]],
                    self.pools[names[pb]], jnp.asarray(arr), op=int(op),
                    block_axis=self.block_axis)
                kfd.notify_launch(self.max_requests, 1, "legacy_bitwise")
                launches += 1
            i = j
        return launches


def _chunks(seq, n):
    for i in range(0, len(seq), n):
        yield seq[i:i + n]


@functools.partial(jax.jit, donate_argnums=(0,))
def _psm_jit(pool, ids):
    rows = pool[jnp.clip(ids[:, 0], 0, pool.shape[0] - 1)]
    safe_dst = jnp.where(ids[:, 1] >= 0, ids[:, 1], pool.shape[0])
    return pool.at[safe_dst].set(rows, mode="drop")


@functools.partial(jax.jit, donate_argnums=(0,))
def _fpm_axis1_jit(pool, ids):
    """Layer-stacked pools (L, nblk, ...): one gather/scatter over axis 1 —
    lowers to L independent local DMAs on TPU (no compute)."""
    rows = pool[:, jnp.clip(ids[:, 0], 0, pool.shape[1] - 1)]
    safe_dst = jnp.where(ids[:, 1] >= 0, ids[:, 1], pool.shape[1])
    return pool.at[:, safe_dst].set(rows, mode="drop")


@functools.partial(jax.jit, donate_argnums=(0,))
def _baseline_axis1_jit(pool, ids):
    rows = pool[:, jnp.clip(ids[:, 0], 0, pool.shape[1] - 1)]
    rows = (rows.astype(jnp.float32) * 1.0).astype(pool.dtype)
    safe_dst = jnp.where(ids[:, 1] >= 0, ids[:, 1], pool.shape[1])
    return pool.at[:, safe_dst].set(rows, mode="drop")


@functools.partial(jax.jit, donate_argnums=(0,))
def _cross_axis1_jit(dst_pool, src_pool, ids):
    """Layer-stacked pool→pool copy: gather/scatter over the block axis 1."""
    rows = src_pool[:, jnp.clip(ids[:, 0], 0, src_pool.shape[1] - 1)]
    safe_dst = jnp.where(ids[:, 1] >= 0, ids[:, 1], dst_pool.shape[1])
    return dst_pool.at[:, safe_dst].set(rows.astype(dst_pool.dtype),
                                        mode="drop")


# no donation: dst_pool may BE a_pool/b_pool (same-pool AND is common) and
# donating an aliased input would invalidate the surviving reference
@functools.partial(jax.jit, static_argnames=("op", "block_axis"))
def _bitwise_jit(dst_pool, a_pool, b_pool, ids, *, op, block_axis):
    """Legacy fan-out bitwise combine: gather both source rows, combine
    through a same-width unsigned bitcast, scatter to dst (``ids``:
    (m, 3) ``[a, b, dst]`` local rows, -1 disables a slot)."""
    ba = block_axis

    def gather(pool, idx):
        cl = jnp.clip(idx, 0, pool.shape[ba] - 1)
        return pool[cl] if ba == 0 else pool[:, cl]

    au = kfd._bitcast_uint(gather(a_pool, ids[:, 0]))
    bu = kfd._bitcast_uint(gather(b_pool, ids[:, 1]))
    if op == OP_AND:
        ru = au & bu
    elif op == OP_OR:
        ru = au | bu
    else:
        ru = ~au
    rows = jax.lax.bitcast_convert_type(ru, dst_pool.dtype)
    safe = jnp.where(ids[:, 2] >= 0, ids[:, 2], dst_pool.shape[ba])
    if ba == 0:
        return dst_pool.at[safe].set(rows, mode="drop")
    return dst_pool.at[:, safe].set(rows, mode="drop")


@functools.partial(jax.jit, donate_argnums=(0,))
def _zero_axis1_jit(pool, ids):
    safe = jnp.where(ids >= 0, ids, pool.shape[1])
    fill = jnp.zeros((pool.shape[0], ids.shape[0]) + pool.shape[2:],
                     pool.dtype)
    return pool.at[:, safe].set(fill, mode="drop")
