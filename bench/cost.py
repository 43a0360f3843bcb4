"""Operations and bytes the served work needs, from the configuration
file's numbers alone (nothing is read from the program, so no change to
the program can move the yardstick).

The architecture's own counts come from the configuration's module in
``bench/references/``: the weights one token multiplies by, the attention
FLOPs per (query, key) pair, the bytes of one KV block in one pool and the
number of primary pools.  This module combines them into the work of a
decoded token, of a prefill and of the drain's rows.

FLOPs count one multiply-add as 2 and only the work the model requires:
each weight a token meets once, plus attention over the context the token
sees.  The embedding lookup is not a matmul and is not counted.
"""
from __future__ import annotations

from typing import Dict

from bench import spec


def decode_flops(m: dict, context: int) -> float:
    """One decoded token that attends over ``context`` positions
    (itself included)."""
    ref = spec.reference(m)
    return 2.0 * ref.matmul_weights_per_token(m) \
        + ref.attn_flops_per_pair(m) * context


def prefill_flops(m: dict, prompt: int) -> float:
    """A prompt of ``prompt`` tokens under a causal mask: token ``i``
    attends over ``i + 1`` positions."""
    ref = spec.reference(m)
    pairs = prompt * (prompt + 1) // 2
    return 2.0 * ref.matmul_weights_per_token(m) * prompt \
        + ref.attn_flops_per_pair(m) * pairs


#: HBM bytes one drain row needs, in blocks of one pool: (reads, writes).
#: Plain opcodes act on every primary pool, cross-pool and bitwise rows on
#: one pool.  A zero-init needs only its write.
ROW_BLOCKS = {
    "fpm_copy": (1, 1, "primary"),
    "psm_copy": (1, 1, "primary"),
    "baseline_copy": (1, 1, "primary"),
    "zero_init": (0, 1, "primary"),
    "cross_pool_copy": (1, 1, "one"),
    "and": (2, 1, "one"),
    "or": (2, 1, "one"),
    "not": (1, 1, "one"),
}


def drain_bytes(m: dict, page: int, rows: Dict[str, float]) -> float:
    """HBM bytes the drain's rows need, by opcode mnemonic."""
    ref = spec.reference(m)
    blk = ref.kv_block_bytes(m, page)
    primary = ref.primary_pools(m)
    total = 0.0
    for op, n in rows.items():
        if op not in ROW_BLOCKS:
            raise KeyError(f"no byte count for drain opcode {op!r}")
        r, w, pools = ROW_BLOCKS[op]
        mult = primary if pools == "primary" else 1
        total += n * (r + w) * blk * mult
    return total
