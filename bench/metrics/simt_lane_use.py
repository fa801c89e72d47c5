"""Share of the 32 lanes that the executed warp-instructions ran on, in %.

Divergence idles lanes inside a warp: a branch that splits a warp runs
each side with part of its lanes, and in a loop whose trip count differs
by lane the lanes that left wait at the exit while the rest go round.
Over every program ``dispatch`` span of the window: its
``device-execute`` groups' ``lanes`` (active lanes summed over the
warp-instructions their real blocks executed) over 32 times their
``winstr``.  These are device counts, so the traced slice does not
disturb them.  ``smstep_lane_use`` is the block-level share; this is the
lane-level one it cannot see.  A program whose spans carry no ``winstr``
reads nothing."""
from bench.readings import spans

LANES = 32


def read(run):
    groups = [g.attrs for _, _, sp in spans(run, "dispatch",
                                            whole_window=True)
              for g in sp.children
              if g.name == "device-execute" and "winstr" in g.attrs]
    winstr = sum(g["winstr"] for g in groups)
    if not winstr:
        return None
    return 100.0 * sum(g["lanes"] for g in groups) / (LANES * winstr)
