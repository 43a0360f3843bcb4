"""Whole round, open loop: the 95th percentile of every inter-token gap
whose later token landed in the window (host clock of the driving loop).
In chat about 5% of gaps hold a prefill or a resume in their round, so
this percentile sits on the edge between those and plain decode rounds
and reads one or the other from run to run."""
from bench.stats import percentile


def read(run):
    gaps = run["host"]["itl_s"]
    if not gaps:
        return None
    return percentile(gaps, 95) * 1e3
