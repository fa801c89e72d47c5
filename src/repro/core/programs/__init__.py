"""The paper's five CUDA benchmarks, hand-compiled to the mini-ISA.

bitonic sort, autocorrelation, matrix multiplication, parallel reduction
and transpose (ERCBench / NVIDIA programmer's guide §5).  Each module
exposes:

  ``build(n) -> np.ndarray``          the kernel binary
  ``launch(n) -> (grid, block_dim)``  launch geometry
  ``make_gmem(rng, n) -> np.ndarray`` initial global memory
  ``oracle(gmem0, n) -> np.ndarray``  expected final global memory region
  ``out_slice(n) -> slice``           where the kernel writes its result
  ``n_threads(n) -> int``             total threads launched (scalar model)

Binary-compatibility note: every kernel is padded to PROGRAM_PAD
instructions, so all five run on ONE jit of the interpreter — the
paper's "same FPGA bitstream runs all five benchmarks" claim, verbatim.
"""
from . import autocorr, bitonic, matmul, reduction, transpose

PROGRAM_PAD = 96

ALL = {
    "autocorr": autocorr,
    "bitonic": bitonic,
    "matmul": matmul,
    "reduction": reduction,
    "transpose": transpose,
}


def compiled_kernels():
    """The DSL-compiled kernel modules (histogram, scan, ELL and CSR
    spmv) — same
    ``build/launch/make_gmem/oracle/out_slice/n_threads`` interface as
    the hand-written five, but authored in the ``repro.compiler`` front
    end and compiled at build() time.  Imported lazily so ``core`` has
    no hard dependency on the compiler layer."""
    from ...compiler.kernels import COMPILED
    return dict(COMPILED)
