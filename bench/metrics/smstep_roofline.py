"""The SM step's share of its roofline, in %.

Kernel time: the device runs of the ``_run_positions`` program inside
the program's ``dispatch`` spans that lie wholly inside the traced
slice.  A ``dispatch`` span runs one sub-batch, every block of every
launch it names, and ends with the sub-batch's counter fetch, so it
holds all of that sub-batch's runs; each such span is credited the
reference warp-instructions of its launches.  Least time: that work's
architectural bytes -- (read ports + 1) x 32 lanes x 4 B per warp-
instruction -- over the chip's peak HBM bandwidth.  The bytes bound the
step: it does no arithmetic worth a compute roof."""
from bench.readings import spans

LANES, WORD = 32, 4


def bytes_per_winstr(machine) -> int:
    return (int(machine["num_read_operands"]) + 1) * LANES * WORD


def read(run):
    if not run.trace or run.to_ns is None or run.slice is None:
        return None
    runs = [(a, b) for name, a, b in run.trace["module_runs"]
            if "_run_positions" in name]
    lo_s, hi_s = run.to_ns(run.slice[0]), run.to_ns(run.slice[1])
    by_ticket = {r.ticket: r for r in run.launches if r.result is not None}
    credited, work, kernel_ns = set(), 0.0, 0.0
    # newest first: a sub-batch that failed and ran again counts once
    for a, b, sp in sorted(spans(run, "dispatch", whole_window=True),
                           key=lambda x: -x[0]):
        lo, hi = run.to_ns(a), run.to_ns(b)
        tickets = [t for t in sp.attrs.get("tickets", ())
                   if t in by_ticket and t not in credited]
        if not (lo_s <= lo and hi <= hi_s) or not tickets:
            continue
        inside = [(s, e) for s, e in runs if lo <= s and e <= hi]
        if not inside:
            continue
        credited.update(tickets)
        work += sum(run.items[by_ticket[t].item].weight for t in tickets)
        kernel_ns += sum(e - s for s, e in inside)
    if kernel_ns <= 0:
        return None
    least_s = work * bytes_per_winstr(run.machine) / \
        run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (kernel_ns * 1e-9)
