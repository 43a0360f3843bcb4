"""Device, closed loop: share of the traced slice in which no operation ran
on the chip (averaged over the chips used)."""


def read(run):
    t = run["trace"]
    if t["window_s"] <= 0:
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * 100.0
