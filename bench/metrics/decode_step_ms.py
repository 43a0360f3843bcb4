"""Model step, decode: device time per execution of the decode program
(one token for every live sequence) in the traced slice."""
from bench.trace_reduce import program_time


def read(run):
    n, secs = program_time(run["trace"], "decode_fn")
    if n == 0:
        return None
    return secs / n * 1e3
