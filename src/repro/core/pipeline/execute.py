"""Execute stage of the all-warp pipeline — the pluggable SP array.

A backend is a pure function of decoded operands: it receives the
per-warp opcode vector plus the pre-gathered (W, 32) lane operands and
returns the ALU result and the ISETP flag nibble for every lane.  Two
backends implement the contract:

* ``"jnp"``    — a vectorized select-by-opcode in plain jnp; runs
  anywhere, and is what XLA specializes per ``MachineConfig`` (removing
  the multiplier really deletes the multiply from the compiled code).
* ``"pallas"`` — the :func:`repro.kernels.simt_alu.simt_alu` VPU kernel:
  the same datapath as a Pallas TPU kernel over (warps, lanes) tiles in
  VMEM.  Interpret mode is not configured: :func:`interpret_mode`
  derives it from the platform (the CPU only), so on the TPU the kernel
  always lowers through Mosaic and a compiler refusal propagates.

Memory loads are *not* part of the backend contract — LDG/LDS data is
gathered by the Read stage (it needs the memory state) and merged here
by opcode, so a backend stays a pure operand->result function.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .. import isa
from .state import MachineConfig
from .fetch_decode import Decoded
from .read import Operands


def interpret_mode() -> bool:
    """Whether Pallas kernels run in interpret mode: on the CPU only.

    The one platform decision of the pipeline, read at trace time by the
    execute-stage dispatch (``"pallas"``) and the step dispatch
    (``"pallas_fused"``).  Anywhere else the kernel is compiled, and a
    refusal from the compiler is raised; nothing falls back to the
    interpreter or to the jnp datapath."""
    return jax.default_backend() == "cpu"


def _execute_jnp(cfg: MachineConfig, dec: Decoded,
                 ops: Operands) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pure-jnp datapath — delegates to the kernel oracle so the
    select-by-opcode ALU exists exactly once outside the Pallas
    kernel (repro.kernels.ref is the single source of truth)."""
    from repro.kernels.ref import simt_alu_ref
    return simt_alu_ref(
        dec.op, ops.s1, ops.s2, ops.s3,
        ops.cond_val.astype(jnp.int32), ops.s2r_val,
        ops.exec_mask.astype(jnp.int32),
        enable_mul=cfg.enable_mul,
        num_read_operands=cfg.num_read_operands)


def _execute_pallas(cfg: MachineConfig, dec: Decoded,
                    ops: Operands) -> Tuple[jnp.ndarray, jnp.ndarray]:
    from repro.kernels.simt_alu import simt_alu
    return simt_alu(
        dec.op, ops.s1, ops.s2, ops.s3,
        ops.cond_val.astype(jnp.int32), ops.s2r_val,
        ops.exec_mask.astype(jnp.int32),
        enable_mul=cfg.enable_mul,
        num_read_operands=cfg.num_read_operands,
        interpret=interpret_mode())


#: backend name -> (cfg, Decoded, Operands) -> (result, isetp nibble)
EXECUTE_STAGE_BACKENDS = {
    "jnp": _execute_jnp,
    "pallas": _execute_pallas,
    # "reference" reuses the jnp datapath inside the single-warp issue
    # loop (pipeline.reference); it never reaches this dispatch.
}


def execute(cfg: MachineConfig, dec: Decoded,
            ops: Operands) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Run the configured backend and merge the memory read ports."""
    backend = EXECUTE_STAGE_BACKENDS[cfg.execute_backend]
    result, nib = backend(cfg, dec, ops)
    opb = dec.op[:, None]
    result = jnp.where(opb == isa.LDG, ops.ld_g,
                       jnp.where(opb == isa.LDS, ops.ld_s, result))
    return result, nib
