"""Model step, prefill: device time of the prefill programs in the traced
slice per thousand prompt tokens they prefilled."""
from bench.trace_reduce import program_time


def read(run):
    n, secs = program_time(run["trace"], "prefill_stage_fn")
    tokens = run["host"]["traced_prefill_tokens"]
    if n == 0 or tokens == 0:
        return None
    return secs * 1e3 / tokens * 1e3
