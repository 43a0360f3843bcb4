"""Bulk movement: the fused drain kernel's share of its roofline.  The
bytes its rows need (``bench/cost.py``, from the per-opcode row counts
the engine keeps) over its device time, against the chip's HBM peak.
A copy is bound by bandwidth alone, so bytes set the roofline."""
from bench.cost import drain_bytes
from bench.trace_reduce import program_time


def read(run):
    n, secs = program_time(run["trace"], "fused_dispatch")
    rows = run["host"]["traced_drain_rows"]
    if n == 0 or secs <= 0 or not rows:
        return None
    need = drain_bytes(run["model"], run["page"], rows)
    return need / secs / run["peaks"]["hbm_bytes_per_s"] * 100.0
