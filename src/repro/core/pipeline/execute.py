"""Execute stage of the all-warp pipeline — the SP array.

:func:`sp_alu` is a pure function of decoded operands: it receives the
per-warp opcode vector plus the pre-gathered (W, 32) lane operands and
returns the ALU result and the ISETP flag nibble for every lane, as a
vectorized select-by-opcode in plain jnp that XLA specializes per
``MachineConfig`` (removing the multiplier really deletes the multiply
from the compiled code).

Memory loads are *not* part of the ALU — LDG/LDS data is gathered by
the Read stage (it needs the memory state) and merged here by opcode,
so the ALU stays a pure operand->result function.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from .. import isa
from .state import MachineConfig
from .fetch_decode import Decoded
from .read import Operands


def sp_alu(op, s1, s2, s3, cond, s2r, mask, *, enable_mul: bool,
           num_read_operands: int):
    """The SP array's datapath over (W, 32) lanes: ``op`` (W,) per warp,
    ``cond`` and ``mask`` int32 lane flags.  Returns ``(result, isetp
    nibble)``, both 0 on masked-off lanes.  ``enable_mul`` and
    ``num_read_operands`` are the §4.2 customization axes: without the
    multiplier IMUL and IMAD give 0, without the third read port IMAD
    does."""
    opb = op[:, None]
    condb = cond != 0
    sh = s2 & 31
    u1 = s1.astype(jnp.uint32)
    mul = (s1 * s2) if enable_mul else jnp.zeros_like(s1)
    mad = (s1 * s2 + s3) if (enable_mul and num_read_operands >= 3) \
        else jnp.zeros_like(s1)
    res = jnp.select(
        [opb == o for o in (isa.MOV, isa.IADD, isa.ISUB, isa.IMUL,
                            isa.IMAD, isa.IMIN, isa.IMAX, isa.IABS,
                            isa.AND, isa.OR, isa.XOR, isa.NOT, isa.SHL,
                            isa.SHR, isa.SAR, isa.ISET, isa.SELP,
                            isa.S2R)],
        [s2, s1 + s2, s1 - s2, mul, mad, jnp.minimum(s1, s2),
         jnp.maximum(s1, s2), jnp.abs(s1), s1 & s2, s1 | s2, s1 ^ s2,
         ~s1, (u1 << sh.astype(jnp.uint32)).astype(jnp.int32),
         (u1 >> sh.astype(jnp.uint32)).astype(jnp.int32), s1 >> sh,
         condb.astype(jnp.int32), jnp.where(condb, s1, s2), s2r],
        jnp.zeros_like(s1))
    d = s1 - s2
    nib = ((d < 0).astype(jnp.int32)
           | ((d == 0).astype(jnp.int32) << 1)
           | ((u1 < s2.astype(jnp.uint32)).astype(jnp.int32) << 2)
           | ((((s1 ^ s2) & (s1 ^ d)) < 0).astype(jnp.int32) << 3))
    m = mask != 0
    return (jnp.where(m, res, 0),
            jnp.where(m & (opb == isa.ISETP), nib, 0))


def execute(cfg: MachineConfig, dec: Decoded,
            ops: Operands) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Run the SP array and merge the memory read ports."""
    result, nib = sp_alu(
        dec.op, ops.s1, ops.s2, ops.s3,
        ops.cond_val.astype(jnp.int32), ops.s2r_val,
        ops.exec_mask.astype(jnp.int32),
        enable_mul=cfg.enable_mul,
        num_read_operands=cfg.num_read_operands)
    opb = dec.op[:, None]
    result = jnp.where(opb == isa.LDG, ops.ld_g,
                       jnp.where(opb == isa.LDS, ops.ld_s, result))
    return result, nib
