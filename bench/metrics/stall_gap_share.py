"""Scheduler: the share of inter-token gaps in the window longer than 1.5
times their median (host clock of the driving loop): the tokens held up
by a prefill, a resume or a preemption in their round."""
from bench.stats import percentile


def read(run):
    gaps = run["host"]["itl_s"]
    if not gaps:
        return None
    cut = 1.5 * percentile(gaps, 50)
    return sum(1 for g in gaps if g > cut) / len(gaps) * 100.0
