"""Mean host ms of the executor's own work per sub-batch: the
``prepare``, ``device-execute`` and ``to-results`` children of each
program ``dispatch`` span, without the wait for the device inside its
``counter-sync``.  A program whose ``dispatch`` spans have no
``prepare`` child reads nothing.  One reader for every cell kind's
``executor_host_ms.<kind>``."""
import numpy as np

from bench.readings import spans

PARTS = ("prepare", "device-execute", "to-results")


def read(run):
    own = [sum(c.t1 - c.t0 for c in sp.children if c.name in PARTS)
           for _, _, sp in spans(run, "dispatch")
           if any(c.name == "prepare" for c in sp.children)]
    return float(np.mean(own)) * 1e3 if own else None
