"""Bundled DSL kernels, compiled on demand.

Four workloads the hand-written benchmark set lacks — histogram,
inclusive prefix scan, ELL-format SpMV and CSR-format SpMV — authored
in the :mod:`repro.compiler.dsl` front end and compiled through the
full pipeline at ``build()`` time (compilation is milliseconds; the
binary then runs on the already-jitted machine, the paper's
under-a-second CUDA-compile story end to end).

ELL SpMV pads every row to ``KMAX`` entries so its loop is uniform;
CSR SpMV (SHOC's ``Spmv``, the CSR-scalar kernel) loops over each
row's own entries, so its trip count differs by lane.  Such a
*divergent loop* compiles to the warp-stack protocol the machine
already has: one SSY in the preheader pushes the loop's exit as the
reconvergence point, the header's top test ``@LT BRA body`` diverges
when some lanes leave, and the ``.S``-labelled exit placed right after
the test pops the TAKEN entry of the lanes that go on, so the stack
holds one entry per loop whatever the trip count.  A test that branched
to the exit, as a uniform loop's does, would leave one TAKEN entry of
the leaving lanes stacked per divergent trip and overflow the 32-entry
warp stack after about 30 trips.

Each module mirrors the paper-benchmark interface of
:mod:`repro.core.programs` (``build / launch / make_gmem / oracle /
out_slice / n_threads``), so the serving CLI, the benchmarks and the
differential server tests treat compiled tenants exactly like the
legacy five.  Binaries are left *unpadded*: the registry buckets them
(64-instr bucket, vs the legacy kernels' 96), so a mixed workload
really exercises heterogeneous footprints.
"""
from . import histogram, scan, spmv, spmv_csr

#: name -> module, the compiled analogue of ``core.programs.ALL``
COMPILED = {
    "histogram": histogram,
    "scan": scan,
    "spmv": spmv,
    "spmv_csr": spmv_csr,
}
