"""Share of the SM step's block-slot steps that ran a real block, in %.

A dispatch group runs ``width`` blocks through one batched loop, which
makes ``trips`` trips, until its longest block ends; a padded duplicate,
or a block that ended sooner, idles in its slot.  Over every program
``dispatch`` span of the window: its ``device-execute`` groups'
``useful_steps`` over their ``trips`` x ``width``.  These are device
counts, so the traced slice does not disturb them.  A program whose
spans carry no ``trips`` reads nothing."""
from bench.readings import spans


def read(run):
    groups = [g.attrs for _, _, sp in spans(run, "dispatch",
                                            whole_window=True)
              for g in sp.children
              if g.name == "device-execute" and "trips" in g.attrs]
    slots = sum(g["trips"] * g["width"] for g in groups)
    if not slots:
        return None
    return 100.0 * sum(g["useful_steps"] for g in groups) / slots
