"""Device microseconds per trip of the SM step's batched loop.

Device time: the runs of the ``_run_positions`` program inside the
program's ``dispatch`` spans that lie wholly inside the traced slice,
credited as ``smstep_roofline`` credits them (newest first, each ticket
once).  Trips: the ``trips`` each credited span carries, its dispatch
groups' loop trips summed.  One trip runs one step of every block of a
group in lockstep, so this is the cost a per-op change to the step
moves.  A program whose spans carry no ``trips`` reads nothing."""
from bench.readings import spans


def read(run):
    if not run.trace or run.to_ns is None or run.slice is None:
        return None
    runs = [(a, b) for name, a, b in run.trace["module_runs"]
            if "_run_positions" in name]
    lo_s, hi_s = run.to_ns(run.slice[0]), run.to_ns(run.slice[1])
    done = {r.ticket for r in run.launches if r.result is not None}
    credited, trips, kernel_ns = set(), 0, 0.0
    # newest first: a sub-batch that failed and ran again counts once
    for a, b, sp in sorted(spans(run, "dispatch", whole_window=True),
                           key=lambda x: -x[0]):
        lo, hi = run.to_ns(a), run.to_ns(b)
        tickets = [t for t in sp.attrs.get("tickets", ())
                   if t in done and t not in credited]
        if not (lo_s <= lo and hi <= hi_s) or not tickets or \
                "trips" not in sp.attrs:
            continue
        inside = [(s, e) for s, e in runs if lo <= s and e <= hi]
        if not inside:
            continue
        credited.update(tickets)
        trips += sp.attrs["trips"]
        kernel_ns += sum(e - s for s, e in inside)
    return kernel_ns * 1e-3 / trips if trips > 0 else None
