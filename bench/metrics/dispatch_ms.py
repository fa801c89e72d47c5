"""Mean host ms of one sub-batch's dispatch (program span ``dispatch``);
it ends with the sub-batch's counter fetch, so it waits for the device.
One reader for every cell kind's ``dispatch_ms.<kind>``."""
import numpy as np

from bench.readings import spans


def read(run):
    d = spans(run, "dispatch")
    return float(np.mean([b - a for a, b, _ in d])) * 1e3 if d else None
