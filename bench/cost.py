"""Operations and bytes the served work needs, from the configuration
file's numbers alone (nothing is read from the program, so no change to
the program can move the yardstick).

FLOPs count one multiply-add as 2 and only the work the model requires:
each weight a token meets once (for a mixture of experts, the router, its
``k`` routed experts and the shared experts, not the buffer slots a
capacity-buffer dispatch also computes), plus attention over the context
the token sees.  The embedding lookup is not a matmul and is not counted.
"""
from __future__ import annotations

from typing import Dict

BF16 = 2


def matmul_weights_per_token(m: dict) -> int:
    """Weights one token multiplies by, over every layer and the head."""
    d, h, kvh, hd = (m["hidden_size"], m["num_attention_heads"],
                     m["num_key_value_heads"], m["head_dim"])
    attn = d * h * hd + 2 * d * kvh * hd + h * hd * d
    if m["family"] == "moe":
        f = m["moe_intermediate_size"]
        ffn = d * m["n_routed_experts"] \
            + 3 * d * f * (m["num_experts_per_tok"] + m["n_shared_experts"])
    else:
        ffn = 3 * d * m["intermediate_size"]
    v = m["vocab_size"]
    return m["num_hidden_layers"] * (attn + ffn) + d * v


def _attn_per_ctx(m: dict) -> int:
    """FLOPs of attention per (query, key) pair over every layer: the
    score and the weighted value, 2 FLOPs per multiply-add each."""
    return 4 * m["num_hidden_layers"] * m["num_attention_heads"] * m["head_dim"]


def decode_flops(m: dict, context: int) -> float:
    """One decoded token that attends over ``context`` positions
    (itself included)."""
    return 2.0 * matmul_weights_per_token(m) + _attn_per_ctx(m) * context


def prefill_flops(m: dict, prompt: int) -> float:
    """A prompt of ``prompt`` tokens under a causal mask: token ``i``
    attends over ``i + 1`` positions."""
    pairs = prompt * (prompt + 1) // 2
    return 2.0 * matmul_weights_per_token(m) * prompt \
        + _attn_per_ctx(m) * pairs


def kv_block_bytes(m: dict, page: int) -> int:
    """Bytes of one KV-pool block (every layer's chunk of ``page``
    tokens) in ONE pool, K or V, in bf16."""
    return (m["num_hidden_layers"] * page * m["num_key_value_heads"]
            * m["head_dim"] * BF16)


#: HBM bytes one drain row needs, in blocks of one pool: (reads, writes).
#: Plain opcodes act on every primary pool (K and V), cross-pool and
#: bitwise rows on one pool.  A zero-init needs only its write.
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


def drain_bytes(m: dict, page: int, rows: Dict[str, float],
                primary_pools: int = 2) -> float:
    """HBM bytes the drain's rows need, by opcode mnemonic."""
    blk = kv_block_bytes(m, page)
    total = 0.0
    for op, n in rows.items():
        if op not in ROW_BLOCKS:
            raise KeyError(f"no byte count for drain opcode {op!r}")
        r, w, pools = ROW_BLOCKS[op]
        mult = primary_pools if pools == "primary" else 1
        total += n * (r + w) * blk * mult
    return total
