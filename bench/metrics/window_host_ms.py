"""Host ms per drain window outside its dispatch groups: the mean self
time of the program's ``window`` spans minus their ``dispatch``
children.  One reader for every cell kind's ``window_host_ms.<kind>``."""
import numpy as np

from bench.readings import spans


def read(run):
    ws = spans(run, "window")
    if not ws:
        return None
    own = [(b - a) - sum(c.t1 - c.t0 for c in sp.children
                         if c.name == "dispatch") for a, b, sp in ws]
    return float(np.mean(own)) * 1e3
