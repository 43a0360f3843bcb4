"""Whole round, open loop: model FLOPs of the prefill and decode tokens
the window's rounds served (``bench/cost.py``) over the rounds' wall time
on the host clock, as a share of the chip's bf16 peak."""


def read(run):
    h = run["host"]
    if h["rounds_s"] <= 0 or h["flops"] <= 0:
        return None
    return h["flops"] / h["rounds_s"] / run["peaks"]["bf16_flops_per_s"] \
        * 100.0
