"""Scheduler: 90th percentile of the wait from a request's due time to the
round that admitted it, over every request admitted in the window (host
clock of the driving loop)."""
from bench.stats import percentile


def read(run):
    waits = run["host"]["queue_wait_s"]
    if not waits:
        return None
    return percentile(waits, 90) * 1e3
