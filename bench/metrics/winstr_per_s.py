"""Reference warp-instructions completed per second of the window.

Each launch counts its item's fixed weight (``bench/weights``).  A
batch window is whole passes; an open-loop window counts the launches
resolved before it closed."""


def read(run):
    done = sum(run.items[r.item].weight for r in run.launches
               if r.result is not None and run.t0 <= r.t_done <= run.t1)
    return done / (run.t1 - run.t0)
