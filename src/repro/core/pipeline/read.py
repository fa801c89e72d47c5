"""Read stage of the all-warp pipeline.

The parallel source-operand units of §4.2, widened to the full (W, 32)
lane grid: register-file reads for up to three source operands per
warp (the third gated by ``num_read_operands``), guard-predicate LUT
evaluation, special-register materialization for S2R, and the memory
read ports (global + shared loads are issued here so the execute stage
is a pure function of operands — that is what makes it pluggable).

The register and predicate files are read by a one-hot masked sum over
their minor axis, the mirror of the write stage's select: each
(warp, lane) reads one column of its own row, so exactly one term of the
sum is nonzero and the int32 result is exact.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from .. import isa
from .state import MachineConfig, SMState
from .fetch_decode import Decoded


class Operands(NamedTuple):
    cond_val: jnp.ndarray   # (W, 32) bool — guard LUT output per lane
    exec_mask: jnp.ndarray  # (W, 32) bool — lanes that execute
    s1: jnp.ndarray         # (W, 32) int32
    s2: jnp.ndarray         # (W, 32) int32
    s3: jnp.ndarray         # (W, 32) int32
    s2r_val: jnp.ndarray    # (W, 32) int32 — selected special register
    gaddr: jnp.ndarray      # (W, 32) int32 — clipped global address
    saddr: jnp.ndarray      # (W, 32) int32 — clipped shared address
    ld_g: jnp.ndarray       # (W, 32) int32 — global load data
    ld_s: jnp.ndarray       # (W, 32) int32 — shared load data


def _read_col(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """table (W, 32, R), idx (W,) -> (W, 32) column ``idx[w]`` per warp;
    an index outside the file reads 0.

    The column iota is built at trace time (Pallas kernel bodies reject
    captured array constants — fused.py traces this stage in-kernel)."""
    cols = jnp.arange(table.shape[2], dtype=jnp.int32)
    hit = cols == idx[:, None, None]
    return jnp.sum(jnp.where(hit, table, 0), axis=2, dtype=table.dtype)


def read_operands(cfg: MachineConfig, lut: jnp.ndarray,
                  block_dim_xy: jnp.ndarray, block_xy: jnp.ndarray,
                  grid_xy: jnp.ndarray, st: SMState,
                  dec: Decoded) -> Operands:
    W = st.pc.shape[0]
    G = st.gmem.shape[0] - 1
    arange_w = jnp.arange(W, dtype=jnp.int32)

    # ---- guard / condition evaluation (predicate LUT of Fig. 2) -------
    nib = _read_col(st.pred, dec.gpred)                  # (W, 32)
    cond_val = lut[dec.gcond[:, None], nib]              # (W, 32) bool
    gm = jnp.where(dec.guarded[:, None], cond_val, True)
    exec_mask = dec.active & st.alive & gm & dec.exec_this[:, None]

    # ---- register-file read ports --------------------------------------
    imm_col = dec.imm[:, None]
    s1 = jnp.where((dec.flags[:, None] & isa.FLAG_SRC1_IMM) != 0, imm_col,
                   _read_col(st.regs, dec.src1))
    s2 = jnp.where((dec.flags[:, None] & isa.FLAG_SRC2_IMM) != 0, imm_col,
                   _read_col(st.regs, dec.src2))
    s3 = _read_col(st.regs, dec.src3) if cfg.num_read_operands >= 3 \
        else jnp.zeros_like(s1)

    # ---- special-register values for S2R -------------------------------
    # lane iota built at trace time (Pallas kernel bodies reject
    # captured array constants — fused.py traces this stage in-kernel)
    lanes = jnp.arange(isa.WARP_SIZE, dtype=jnp.int32)
    tid_flat = arange_w[:, None] * 32 + lanes[None, :]   # (W, 32)
    bdx, bdy = block_dim_xy[0], block_dim_xy[1]
    shape = (W, isa.WARP_SIZE)
    srs = jnp.stack([
        tid_flat % bdx, tid_flat // bdx,          # tidx, tidy
        jnp.broadcast_to(block_xy[0], shape),     # ctax
        jnp.broadcast_to(block_xy[1], shape),     # ctay
        jnp.broadcast_to(bdx, shape),             # ntidx
        jnp.broadcast_to(bdy, shape),             # ntidy
        jnp.broadcast_to(grid_xy[0], shape),      # nctax
        jnp.broadcast_to(grid_xy[1], shape),      # nctay
        tid_flat,                                 # flat tid
        jnp.broadcast_to(block_xy[1] * grid_xy[0] + block_xy[0], shape),
        jnp.broadcast_to(bdx * bdy, shape),       # flat block size
    ]).astype(jnp.int32)                          # (11, W, 32)
    s2r_val = srs[jnp.clip(dec.imm, 0, srs.shape[0] - 1), arange_w]

    # ---- memory read ports ----------------------------------------------
    addr = s1 + imm_col
    gaddr = jnp.clip(addr, 0, G - 1)
    saddr = jnp.clip(addr, 0, cfg.smem_words - 1)
    ld_g = st.gmem[gaddr]
    ld_s = st.smem[saddr]

    return Operands(cond_val=cond_val, exec_mask=exec_mask, s1=s1, s2=s2,
                    s3=s3, s2r_val=s2r_val, gaddr=gaddr, saddr=saddr,
                    ld_g=ld_g, ld_s=ld_s)
