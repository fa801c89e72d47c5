"""Share of the traced slice in which no op ran on the device, in %.

One reader for every cell kind's ``device_idle_pct.<kind>``."""


def read(run):
    t = run.trace
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
