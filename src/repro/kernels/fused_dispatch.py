"""Fused command-queue dispatch kernel — the MC's serialized command stream.

RowClone's memory controller accepts a stream of copy/init commands and
executes them back-to-back inside DRAM with no per-command CPU involvement
(§2.3).  The seed engine betrayed that: one device dispatch per mechanism
per pool (up to 8 launches for one mixed request batch).  This kernel is the
TPU analogue of the MC's command queue drain: **one** ``pallas_call`` whose
scalar-prefetched SMEM table is ``(m, 3)`` int32 ``[opcode, src, dst]`` rows;
the grid body switches on the opcode and issues the corresponding HBM→HBM
``make_async_copy`` (copies) or zero-row broadcast DMA (init) on
alternating semaphore slots.  The drain is **overlapped**: each step
starts its DMAs and the wait trails one step behind (the previous step's
descriptors are reconstructed and waited after the current step issues),
so two adjacent commands' DMAs pipeline — the MC keeping its command bus
busy while a copy completes.  Safety is adjacency-local and guaranteed by
the CommandQueue's source-hazard tracking: flushed tables never carry
RAW/WAW pairs at all, and WAR pairs (a row overwriting an earlier row's
source) are kept non-adjacent by spacer rows (``cmdqueue.space_war_rows``).
Multi-pool engines (K and V pages of one KV block) pass every pool to the
same launch; each grid step moves the block in all of them.

Opcodes (also the ``CommandQueue`` tags, core/cmdqueue.py):

  ======================  ==  ==================================================
  ``OP_FPM_COPY``          0  same-slab block copy (FPM — subarray-local DMA)
  ``OP_PSM_COPY``          1  cross-slab copy (PSM; same DMA on a single slab)
  ``OP_BASELINE_COPY``     2  RowClone-disabled copy (mechanism modeling only)
  ``OP_ZERO_INIT``         3  BuZ — broadcast the reserved zero block into dst
  ``OP_CROSS_POOL_COPY``   4  pool-to-pool copy; src/dst are *global* ids
                              ``base[pool] + block`` where ``base`` is the
                              prefix sum of per-pool block counts (the
                              PoolGroup address space, core/poolspec.py) —
                              pools may have DIFFERENT block counts but must
                              share block shape and dtype
  ``OP_AND``               5  in-memory bulk bitwise AND (Ambit TRA analogue):
                              ``src`` packs TWO global ids ``a * total + b``
                              (``total`` = sum of pool block counts), ``dst``
                              is a global id; ``dst = a & b`` bit-for-bit
  ``OP_OR``                6  in-memory bulk bitwise OR, same two-source packing
  ``OP_NOT``               7  in-memory bitwise NOT (``b`` packs equal to ``a``)
  ``OP_NOP``              -1  padding row (bucketed table), also ``dst == -1``
  ======================  ==  ==================================================

Pools carry a per-pool **role vector** (``primary`` tuple of bools): plain
opcodes (0-3) move the named block in every primary pool (all primary pools
share one block count — the allocator's address space); staging pools are
reachable only through ``OP_CROSS_POOL_COPY`` rows that name them in a
global id, and may be any size (e.g. a small staging ring).  The base
offsets are derived from the pool shapes at trace time, so the table
encoding and the kernel always agree.

``block_axis=1`` handles layer-stacked serving pools ``(L, nblk, ...)``: the
grid grows a layer dimension and each command becomes L independent DMAs, as
in the seed's axis-1 path.

CONTRACT (same as the per-mechanism kernels, now per *flush*): within one
table, no row may read a block that an earlier row writes, and no two rows
may write the same block — the CommandQueue's hazard guards auto-flush
before either can occur.  Under that contract sources observe the
pre-flush pool state (the kernel actually reads in place during the
serial drain, which the guards make indistinguishable — and which lets
the pools be aliased in-place with no snapshot copy).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

# the opcode table is DECLARED once, in the core/opcodes.py registry; the
# kernel (like the CommandQueue and the jnp reference) derives its switch
# sets from it.  The names are re-exported here for the long-standing
# import surface (cmdqueue/tests import OP_* from this module).
from repro.core.opcodes import (BITWISE_OPS, OP_AND, OP_BASELINE_COPY,
                                OP_CROSS_POOL_COPY, OP_FPM_COPY, OP_NOP,
                                OP_NOT, OP_OR, OP_PSM_COPY, OP_ZERO_INIT,
                                OPCODE_NAMES, PLAIN_COPY_OPS,
                                pack_bitwise_src, unpack_bitwise_src)

_UINTS = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}


def _op_in(op, values):
    """Fold a registry-derived opcode set into one traced predicate —
    the kernel/reference switch tables stay in lockstep with the
    ``core/opcodes.py`` registry instead of hand-listing members."""
    pred = op == values[0]
    for v in values[1:]:
        pred = pred | (op == v)
    return pred


def _bitcast_uint(arr):
    """Reinterpret ``arr`` as the same-itemsize unsigned-int dtype (a pure
    bitcast): the bitwise opcodes AND/OR/NOT raw bit patterns, so float
    pools combine bytes exactly like the DRAM rows they model."""
    dt = np.dtype(arr.dtype)
    if np.issubdtype(dt, np.unsignedinteger):
        return arr
    return jax.lax.bitcast_convert_type(arr, _UINTS[dt.itemsize])


# ---------------------------------------------------------------------------
# launch accounting — the hook tests and benchmarks use to assert "one
# kernel launch per flush".  Every device dispatch of bulk-movement work
# (fused or legacy per-op) reports here.
# ---------------------------------------------------------------------------

_LAUNCH_HOOKS: List[Callable[[int, int, str], None]] = []
_LAUNCH_COUNT = 0


def add_launch_hook(fn: Callable[[int, int, str], None]) -> None:
    """Register ``fn(n_commands, n_pools, mechanism)`` to fire per launch."""
    _LAUNCH_HOOKS.append(fn)


def remove_launch_hook(fn: Callable[[int, int, str], None]) -> None:
    """Unregister a hook added with :func:`add_launch_hook`."""
    _LAUNCH_HOOKS.remove(fn)


def launch_count() -> int:
    """Cumulative bulk-movement launches this process."""
    return _LAUNCH_COUNT


def notify_launch(n_commands: int, n_pools: int, mechanism: str) -> None:
    """Record one bulk-movement device dispatch (launch accounting).

    Every path that issues device work for queued commands — the fused
    drains, the legacy per-op fan-out, and the seed staging scatter —
    reports here so tests and benchmarks can assert launches/flush."""
    global _LAUNCH_COUNT
    _LAUNCH_COUNT += 1
    for fn in _LAUNCH_HOOKS:
        fn(n_commands, n_pools, mechanism)


# ---------------------------------------------------------------------------
# drain guards — the abort-safe pre-dispatch hook.  The engine's drain loop
# calls check_drain() for every chunk BEFORE the donating dispatch, so a
# guard that raises (fault injection, admission control, backpressure)
# aborts the flush while every pool buffer is still valid — the engine
# stashes the undispatched suffix and recover() can re-drain it.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DrainInfo:
    """One chunk of a flush, about to dispatch.

    ``flush`` is the engine-wide flush index (``engine.next_flush_index``
    names the upcoming one), ``chunk`` the 0-based overflow-chunk ordinal
    within that flush; ``engine`` identifies which engine is draining so
    guards bound to one engine ignore the rest."""

    flush: int        #: engine-wide flush index
    chunk: int        #: overflow-chunk ordinal within the flush (0-based)
    n_commands: int   #: live (non-NOP) rows in this chunk
    n_pools: int      #: pools the dispatch will move
    engine: object = dataclasses.field(default=None, repr=False)


_DRAIN_GUARDS: List[Callable[[DrainInfo], None]] = []


def add_drain_guard(fn: Callable[[DrainInfo], None]) -> None:
    """Register ``fn(DrainInfo)`` to run before every chunk dispatch; a
    guard that raises aborts the flush with pool buffers intact (the
    fault-injection and admission-control hook — runtime/fault.py)."""
    _DRAIN_GUARDS.append(fn)


def remove_drain_guard(fn: Callable[[DrainInfo], None]) -> None:
    """Unregister a guard added with :func:`add_drain_guard`."""
    _DRAIN_GUARDS.remove(fn)


def check_drain(info: DrainInfo) -> None:
    """Run every registered drain guard against one pending chunk
    (called by the engine's drain loop before the donating dispatch)."""
    for fn in list(_DRAIN_GUARDS):
        fn(info)


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

def _make_kernel(n_pools: int, block_axis: int, sizes: Tuple[int, ...],
                 primary: Tuple[bool, ...], overlap: bool):
    """Build the grid body for ``n_pools`` pools with per-pool block counts
    ``sizes`` and role vector ``primary``.  Plain opcodes (FPM/PSM/baseline
    copy, zero-init) move the block in every primary pool; *staging* pools
    (``primary[p] == False``) are reachable only through
    ``OP_CROSS_POOL_COPY`` global ids — bulk movement never touches staged
    bytes it wasn't asked to move.  Cross-pool ids decode against the
    prefix-sum ``bases`` of ``sizes`` (the PoolGroup address space).

    ``overlap=True`` is the OVERLAPPED drain: each step starts its DMAs on
    the parity semaphore slot and the *wait* trails one step behind — the
    previous step's copies are reconstructed (same src/dst/semaphore, the
    standard deferred-wait idiom) and waited only after the current step
    has issued, so up to two steps' DMAs are in flight at once.  The
    safety contract is adjacency-local: consecutive rows must touch
    disjoint blocks.  RAW/WAW never co-exist in one flushed table (the
    CommandQueue guards), and WAR pairs — a row overwriting an earlier
    row's *source* — are kept non-adjacent by the queue's spacer rows
    (cmdqueue.space_war_rows): at the spacer step nothing issues but the
    trailing wait still fires, so the in-flight read completes before the
    write starts.  ``overlap=False`` keeps the serial per-step
    start-then-wait drain (A/B and debugging)."""
    bases = []
    run = 0
    for n in sizes:
        bases.append(run)
        run += n
    total = run

    def kernel(cmds_ref, *refs):
        zeros = refs[:n_pools]
        # refs[n:2n] are the aliased (donated) pool inputs — never touched;
        # both reads and writes go through ``outs`` (in place).  The
        # CommandQueue excludes read-after-write and write-after-write
        # within a table, so in-place source reads equal pre-flush state
        # reads — and no snapshot copy of the pools is ever materialized.
        outs = refs[2 * n_pools:3 * n_pools]
        sem = refs[3 * n_pools]          # DMA semaphore pair, shape (2,)
        va = refs[3 * n_pools + 1]       # VMEM compute scratch (source A)
        vb = refs[3 * n_pools + 2]       # VMEM compute scratch (source B)
        reads = outs

        i = pl.program_id(0)
        if block_axis == 1:
            l = pl.program_id(1)
            L = pl.num_programs(1)
            step = i * L + l
            n_steps = pl.num_programs(0) * L
        else:
            l = None
            L = 1
            step = i
            n_steps = pl.num_programs(0)

        def blk(ref, b, lay):
            return ref.at[lay, b] if block_axis == 1 else ref.at[b]

        def visit(ci, lay, slot, act, issue=True):
            """Apply ``act`` (start / wait / both) to every DMA descriptor
            of command ``ci`` at layer ``lay``, tracked by semaphore slot
            ``slot``.  Reconstructing the descriptors from the SMEM table
            makes the deferred wait possible: the waiting step rebuilds
            the exact copies the issuing step started.

            ``issue=False`` marks the deferred-WAIT phase: bitwise compute
            rows (``OP_AND``/``OP_OR``/``OP_NOT``) run fully synchronously
            at their own step — load both sources into VMEM, combine,
            write back — so they leave NO in-flight descriptors for the
            wait phase to reconstruct and are skipped there."""
            op = cmds_ref[ci, 0]
            s = cmds_ref[ci, 1]
            d = cmds_ref[ci, 2]
            sm = sem.at[slot]

            if issue:
                @pl.when(_op_in(op, BITWISE_OPS) & (d >= 0))
                def _():
                    # two-source compute row: src packs a*total+b; dst is a
                    # global id.  Synchronous DMA round-trip through VMEM —
                    # the deferred-wait overlap skips these rows entirely.
                    a = s // total
                    b = s - a * total
                    for ps in range(n_pools):
                        @pl.when((a >= bases[ps])
                                 & (a < bases[ps] + sizes[ps]))
                        def _(ps=ps):
                            cp = pltpu.make_async_copy(
                                blk(reads[ps], a - bases[ps], lay), va, sm)
                            cp.start()
                            cp.wait()

                        @pl.when((b >= bases[ps])
                                 & (b < bases[ps] + sizes[ps]))
                        def _(ps=ps):
                            cp = pltpu.make_async_copy(
                                blk(reads[ps], b - bases[ps], lay), vb, sm)
                            cp.start()
                            cp.wait()
                    au = _bitcast_uint(va[...])
                    bu = _bitcast_uint(vb[...])
                    ru = jnp.where(op == OP_AND, au & bu,
                                   jnp.where(op == OP_OR, au | bu, ~au))
                    va[...] = jax.lax.bitcast_convert_type(ru, va.dtype)
                    for pd in range(n_pools):
                        @pl.when((d >= bases[pd])
                                 & (d < bases[pd] + sizes[pd]))
                        def _(pd=pd):
                            cp = pltpu.make_async_copy(
                                va, blk(outs[pd], d - bases[pd], lay), sm)
                            cp.start()
                            cp.wait()

            @pl.when((op >= 0) & (d >= 0))
            def _():
                @pl.when(_op_in(op, PLAIN_COPY_OPS))
                def _():
                    for p in range(n_pools):
                        if primary[p]:
                            act(pltpu.make_async_copy(
                                blk(reads[p], s, lay), blk(outs[p], d, lay),
                                sm))

                @pl.when(op == OP_ZERO_INIT)
                def _():
                    for p in range(n_pools):
                        if primary[p]:
                            act(pltpu.make_async_copy(
                                zeros[p].at[0], blk(outs[p], d, lay), sm))

                @pl.when(op == OP_CROSS_POOL_COPY)
                def _():
                    for ps in range(n_pools):
                        for pd in range(n_pools):
                            @pl.when((s >= bases[ps])
                                     & (s < bases[ps] + sizes[ps])
                                     & (d >= bases[pd])
                                     & (d < bases[pd] + sizes[pd]))
                            def _(ps=ps, pd=pd):
                                act(pltpu.make_async_copy(
                                    blk(reads[ps], s - bases[ps], lay),
                                    blk(outs[pd], d - bases[pd], lay), sm))

        if not overlap:
            # serial drain: per-step start+wait back to back (seed shape)
            visit(i, l, step % 2, lambda cp: (cp.start(), cp.wait()))
            return

        # Overlapped drain — issue now, wait one step behind:
        #   step k   : start(k) on sem[k%2]; wait(k-1) on sem[(k-1)%2]
        #   last step: additionally wait(last)
        # Slot k%2 is reused by step k+2, which runs only after step k+1
        # waited step k — so two slots bound the in-flight window to the
        # adjacent pair the spacing contract protects.
        visit(i, l, step % 2, lambda cp: cp.start())
        if block_axis == 1:
            prev_i = (step - 1) // L
            prev_l = (step - 1) % L
        else:
            prev_i, prev_l = i - 1, None

        @pl.when(step > 0)
        def _():
            visit(prev_i, prev_l, (step - 1) % 2, lambda cp: cp.wait(),
                  issue=False)

        @pl.when(step == n_steps - 1)
        def _():
            visit(i, l, step % 2, lambda cp: cp.wait(), issue=False)

    return kernel


def _as_primary(primary: Optional[Tuple[bool, ...]],
                n_pools: int) -> Tuple[bool, ...]:
    """Normalize the per-pool role vector: ``None`` means every pool is
    primary (single-address-space engines); an explicit tuple is validated
    against the pool count.  (The pre-PoolGroup ``n_primary`` int shim is
    gone — callers pass the role vector.)"""
    if primary is None:
        return tuple([True] * n_pools)
    assert len(primary) == n_pools, (primary, n_pools)
    return tuple(bool(p) for p in primary)


def _fused_dispatch_call(cmds, zero_blocks, pools, *, block_axis: int,
                         interpret: bool,
                         primary: Optional[Tuple[bool, ...]] = None,
                         overlap: bool = True):
    """The raw pallas_call — shared by the single-slab jit entry and the
    per-shard body of the sharded entry (already inside a jit there).
    Per-pool block counts (and the global-id base offsets) come from the
    pool shapes, so the call works unchanged on full pools and on
    per-shard slabs.

    ``overlap``: overlapped DMA drain (wait trails one step behind issue).
    Tables must then keep adjacent rows disjoint — tables produced by
    ``CommandQueue.flush`` / ``partition_commands`` are WAR-spaced; direct
    callers handing in raw tables with adjacent write-after-read pairs
    must pass ``overlap=False``."""
    n_pools = len(pools)
    sizes = tuple(int(p.shape[block_axis]) for p in pools)
    primary = _as_primary(primary, n_pools)
    grid = ((cmds.shape[0],) if block_axis == 0
            else (cmds.shape[0], pools[0].shape[0]))
    # one block's worth of VMEM ×2 for the bitwise compute rows (all pools
    # share block shape + dtype — the cross-pool/global-id contract)
    blk_shape = pools[0].shape[block_axis + 1:]
    return pl.pallas_call(
        _make_kernel(n_pools, block_axis, sizes, primary, overlap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (2 * n_pools),
            out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n_pools,
            # one DMA semaphore per in-flight slot: the overlapped drain
            # alternates parity, the serial drain just alternates
            scratch_shapes=[pltpu.SemaphoreType.DMA((2,)),
                            pltpu.VMEM(blk_shape, pools[0].dtype),
                            pltpu.VMEM(blk_shape, pools[0].dtype)],
        ),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # operand order: cmds, zeros (n), donated pools (n); pools are
        # passed ONCE and aliased — the kernel works in place, so no
        # full-pool snapshot copy is inserted by XLA
        input_output_aliases={1 + n_pools + p: p for p in range(n_pools)},
        interpret=interpret,
    )(cmds, *zero_blocks, *pools)


@functools.partial(jax.jit,
                   static_argnames=("block_axis", "interpret", "primary",
                                    "overlap"),
                   donate_argnums=(2,))
def _fused_dispatch_jit(cmds, zero_blocks, pools, *, block_axis: int,
                        interpret: bool,
                        primary: Optional[Tuple[bool, ...]] = None,
                        overlap: bool = True):
    return _fused_dispatch_call(cmds, zero_blocks, pools,
                                block_axis=block_axis, interpret=interpret,
                                primary=primary, overlap=overlap)


def fused_dispatch_pallas(pools: Sequence, zero_blocks: Sequence, cmds, *,
                          block_axis: int = 0, interpret: bool = False,
                          primary: Optional[Tuple[bool, ...]] = None,
                          overlap: bool = True) -> Tuple:
    """Execute one flushed command table over every pool in ONE launch.

    pools:       sequence of (nblk_p, ...) or (L, nblk_p, ...) arrays
                 (donated); block counts may differ per pool — cross-pool
                 ids decode against the prefix-sum bases of those counts
    zero_blocks: per-pool reserved zero row, shape (1,) + block_shape
    cmds:        (m, 3) int32 [opcode, src, dst]; OP_NOP/-1 rows are padding
    primary:     per-pool role vector (True = plain opcodes move the block
                 there; every primary pool shares one block count).  None =
                 every pool is primary.
    overlap:     overlapped DMA drain — the wait trails one step behind
                 issue.  Requires adjacent rows disjoint (queue-flushed
                 tables are WAR-spaced; see ``_fused_dispatch_call``).
    """
    out = _fused_dispatch_jit(
        cmds, tuple(zero_blocks), tuple(pools), block_axis=block_axis,
        interpret=interpret, primary=_as_primary(primary, len(pools)),
        overlap=overlap)
    notify_launch(int(cmds.shape[0]), len(out), "fused")
    return tuple(out)


# ---------------------------------------------------------------------------
# sharded entry — ONE shard_map'd launch drains a whole flush across the mesh
# ---------------------------------------------------------------------------
#
# Each shard scalar-prefetches ITS slab's sub-table (same kernel, same opcode
# switch — the ids are just slab-local) and drains it in place; cross-slab
# commands ride the same launch as a send/recv plan: every shard gathers its
# outgoing blocks from the pre-drain slab state, the buffers hop the mesh via
# ppermute (one permute per hop distance — the LISA fast-inter-slab-link
# analogue), and land with a scatter on the destination shard.  The
# CommandQueue hazard guards make this interleaving exact: transfer sources
# are never written earlier in the table (gather reads pre-flush state),
# transfer destinations are disjoint from every other destination and are
# only read by rows enqueued before the transfer (which drain locally before
# the scatter lands).

def _gather_rows(slab, rows, block_axis):
    cl = jnp.clip(rows, 0, slab.shape[block_axis] - 1)
    return slab[cl] if block_axis == 0 else slab[:, cl]


def _scatter_rows(slab, data, dst, valid, block_axis):
    safe = jnp.where(valid, dst, slab.shape[block_axis])
    if block_axis == 0:
        return slab.at[safe].set(data, mode="drop")
    return slab.at[:, safe].set(data, mode="drop")


@functools.lru_cache(maxsize=256)
def _sharded_runner(mesh, pool_axes: Tuple[str, ...], deltas: Tuple[int, ...],
                    n_pools: int, block_axis: int, use_pallas: bool,
                    interpret: bool, primary: Tuple[bool, ...],
                    replicated: Tuple[bool, ...]):
    """Build (and cache) the jit'd shard_map'd drain for one static plan
    structure.  The jit layer further caches per array shape; table shapes
    are bucketed (cmdqueue.BUCKETS) and decode-round flushes are local-only
    (``deltas=()``).  Adversarial streams churning distinct delta subsets
    are bounded by the signature fold in :func:`sharded_fused_dispatch`:
    past :data:`MAX_DELTA_SIGNATURES` distinct ``(deltas, t)`` signatures,
    plans fold to the full delta set so the compile count stays O(1).

    ``replicated[p]`` marks pools whose block axis is NOT sharded (the
    ``PoolSpec.sharding == ()`` hint — e.g. a small staging ring held
    whole on every device): their in/out specs replicate, each shard sees
    the full pool as its slab, and cross-pool reads from them are always
    slab-local (``partition_commands`` classifies them by the sharded
    side)."""
    n_shards = int(np.prod([mesh.shape[a] for a in pool_axes]))
    axis = pool_axes if len(pool_axes) > 1 else pool_axes[0]
    pspec = P(*([None] * block_axis), axis)
    pool_specs = tuple(P() if replicated[p] else pspec
                       for p in range(n_pools))
    lspec = P(axis, None, None)             # local tables   (S, m, 3)
    sspec = P(None, axis, None)             # send rows      (K, S, t)
    rspec = P(None, axis, None, None)       # recv tables    (K, S, t, 4)

    def body(local_tbl, send_rows, recv_tbl, zeros, pools):
        tbl = local_tbl[0]                  # this shard's (m, 3) sub-table
        slabs = list(pools)
        # 1) gather every transfer source from the PRE-drain slab state
        #    (each pool gathered at the same row; the recv side picks the
        #    buffer that matters)
        bufs = [jnp.stack([_gather_rows(p, send_rows[k, 0], block_axis)
                           for p in slabs])
                for k in range(len(deltas))]
        # 2) drain this slab's sub-table — same kernel, slab-local ids
        #    (cross-pool ids re-stacked against the SLAB shapes' prefix
        #    sums, which is exactly how partition_commands encoded them)
        if use_pallas:
            slabs = list(_fused_dispatch_call(
                tbl, tuple(zeros), tuple(slabs), block_axis=block_axis,
                interpret=interpret, primary=primary))
        else:
            from repro.kernels import ref as kref
            slabs = list(kref.fused_dispatch(slabs, zeros, tbl,
                                             block_axis=block_axis,
                                             primary=primary))
        # 3) hop the buffers, then scatter in TWO phases: phase 0 lands
        #    every overwrite entry (plain transfers, and OP_NOT entries
        #    which invert the buffer in flight), phase 1 folds the
        #    AND/OR combine entries into the phase-0 result.  A two-source
        #    bitwise row whose sources live on different shards ships ONE
        #    entry per source: srcA overwrites dst (phase 0), srcB combines
        #    into it (phase 1) — the phase split orders them even when the
        #    two sources arrive on different hop distances.
        def expand(cond, data):
            shape = [1] * data.ndim
            shape[block_axis] = cond.shape[0]
            return cond.reshape(shape)

        recvs = [jax.lax.ppermute(
                     bufs[k],
                     axis, [(i, (i + delta) % n_shards)
                            for i in range(n_shards)])
                 for k, delta in enumerate(deltas)]
        for phase in (0, 1):
            for k in range(len(deltas)):
                recvd = recvs[k]
                rt = recv_tbl[k, 0]         # (t, 4)
                buf_pool, dst_pool = rt[:, 0], rt[:, 1]
                dst_row, comb = rt[:, 2], rt[:, 3]
                t = rt.shape[0]
                is_comb = (comb == OP_AND) | (comb == OP_OR)
                phase_sel = is_comb if phase else ~is_comb
                for pd in range(n_pools):
                    sel = jnp.where(buf_pool < 0, pd, buf_pool)
                    idx_shape = ((1, t) + (1,) * (recvd.ndim - 2)
                                 if block_axis == 0
                                 else (1, 1, t) + (1,) * (recvd.ndim - 3))
                    picked = jnp.take_along_axis(
                        recvd, sel.reshape(idx_shape), axis=0)[0]
                    picked = picked.astype(slabs[pd].dtype)
                    # whole-block rows (dst_pool < 0) came from plain
                    # opcodes: they land in every PRIMARY pool only —
                    # staging pools take transfers naming them explicitly
                    valid = (dst_row >= 0) & phase_sel & (
                        ((dst_pool < 0) | (dst_pool == pd)) if primary[pd]
                        else (dst_pool == pd))
                    if phase == 0:
                        # select raw bits, not floats, as kernels/ref.py
                        # does
                        pu = _bitcast_uint(picked)
                        data = jax.lax.bitcast_convert_type(
                            jnp.where(expand(comb == OP_NOT, pu), ~pu, pu),
                            picked.dtype)
                    else:
                        cur = _gather_rows(
                            slabs[pd], jnp.where(valid, dst_row, 0),
                            block_axis)
                        cu = _bitcast_uint(cur)
                        pu = _bitcast_uint(picked)
                        ru = jnp.where(expand(comb == OP_AND, cu),
                                       cu & pu, cu | pu)
                        data = jax.lax.bitcast_convert_type(ru, picked.dtype)
                    slabs[pd] = _scatter_rows(slabs[pd], data, dst_row,
                                              valid, block_axis)
        return tuple(slabs)

    mapped = jax.shard_map(
        body, mesh=mesh,
        # P() replicates the zero rows; per-pool specs shard or replicate
        # each pool leaf according to its PoolSpec.sharding hint
        in_specs=(lspec, sspec, rspec, P(), pool_specs),
        out_specs=pool_specs,
        check_vma=False)
    return jax.jit(mapped, donate_argnums=(4,))


#: the hand-picked jit-cache bound (:func:`set_max_delta_signatures`
#: restores it on None)
DEFAULT_MAX_DELTA_SIGNATURES = 8

#: distinct (deltas, t) collective signatures compiled per (mesh, pool
#: structure) before plans fold to the full delta set (jit-cache bound)
MAX_DELTA_SIGNATURES = DEFAULT_MAX_DELTA_SIGNATURES

_DELTA_SIGS: dict = {}


def set_max_delta_signatures(n: Optional[int]) -> int:
    """Retarget the process-wide delta-signature jit-cache bound (``None``
    restores :data:`DEFAULT_MAX_DELTA_SIGNATURES`) — the autotuner's
    knob: a larger bound compiles more collective bodies before folding;
    a smaller one folds (and pads) sooner.  Clears the per-(mesh, pools)
    signature memory so the new bound applies from a clean slate.
    Returns the installed bound."""
    global MAX_DELTA_SIGNATURES
    if n is None:
        MAX_DELTA_SIGNATURES = DEFAULT_MAX_DELTA_SIGNATURES
    else:
        n = int(n)
        if n < 1:
            raise ValueError(f"max_delta_signatures must be >= 1, got {n}")
        MAX_DELTA_SIGNATURES = n
    _DELTA_SIGS.clear()
    return MAX_DELTA_SIGNATURES


def max_delta_signatures() -> int:
    """The current delta-signature bound (see
    :func:`set_max_delta_signatures`)."""
    return MAX_DELTA_SIGNATURES


def _bound_delta_signatures(plan, key):
    """Jit-cache bound for the collective drain: every distinct
    ``(deltas, t)`` plan signature compiles its own shard_map body, and an
    adversarial stream can churn up to ``2^(S-1)`` delta subsets.  Past
    :data:`MAX_DELTA_SIGNATURES` distinct signatures per (mesh, pool
    structure), fold the plan onto the FULL delta set (cmdqueue
    ``fold_shard_plan``) — the folded signature is one shape per slot
    bucket, so the compile count stays O(1) while unseen subsets keep
    draining correctly (their extra ppermutes carry all-padding tables)."""
    if not plan.deltas:
        return plan                 # local-only drain: one signature
    sigs = _DELTA_SIGS.setdefault(key, set())
    sig = (plan.deltas, int(plan.send_rows.shape[2]))
    if sig in sigs:
        return plan
    if len(sigs) < MAX_DELTA_SIGNATURES:
        sigs.add(sig)
        return plan
    from repro.core.cmdqueue import fold_shard_plan
    return fold_shard_plan(plan)


def sharded_fused_dispatch(pools: Sequence, zero_blocks: Sequence, plan, *,
                           mesh, pool_axes: Tuple[str, ...],
                           block_axis: int = 0, use_pallas: bool = False,
                           interpret: bool = False,
                           primary: Optional[Tuple[bool, ...]] = None,
                           replicated: Optional[Tuple[bool, ...]] = None
                           ) -> Tuple:
    """Drain one partitioned flush (a cmdqueue.ShardPlan) as ONE collective
    launch over every pool: per-slab fused sub-table drains + the
    cross-slab send/recv plan, all inside a single shard_map'd dispatch.
    Pools may carry different block counts (each partitions by its own
    shard size — ``plan.shard_sizes``); ``primary`` is the per-pool role
    vector exactly as in :func:`fused_dispatch_pallas`; ``replicated``
    marks pools held whole on every device (``PoolSpec.sharding == ()``
    hints), which must match the plan's partitioning."""
    primary = _as_primary(primary, len(pools))
    if replicated is None:
        replicated = tuple([False] * len(pools))
    plan = _bound_delta_signatures(
        plan, (mesh, tuple(pool_axes), len(pools), block_axis, primary,
               replicated))
    if plan.deltas:
        send = jnp.asarray(plan.send_rows)
        recv = jnp.asarray(plan.recv_tables)
    else:  # no cross-slab traffic: zero-length transfer tables, no permutes
        s = plan.n_shards
        send = jnp.zeros((0, s, 1), jnp.int32)
        recv = jnp.full((0, s, 1, 4), -1, jnp.int32)
    runner = _sharded_runner(mesh, tuple(pool_axes), tuple(plan.deltas),
                             len(pools), block_axis, use_pallas, interpret,
                             primary, tuple(replicated))
    out = runner(jnp.asarray(plan.local_tables), send, recv,
                 tuple(zero_blocks), tuple(pools))
    notify_launch(int(plan.local_tables.shape[1]), len(out), "fused_mesh")
    return tuple(out)
