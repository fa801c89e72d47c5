"""Write stage of the all-warp pipeline.

Commits one lockstep issue for every warp at once.  The register file
and the predicate file are written by a one-hot select over their minor
axis: each (warp, lane) writes at most one column of its own row
(``dst`` / ``pdst`` per warp), so the write is one dense ``where`` over
the (W, 32, R) file, with no scatter.  A TPU scatter pays for every
update, the select only for the file's words, which at these sizes is
the cheaper of the two at every warp count.  Global and shared stores
from all warps flatten to one scatter each, with inactive lanes
redirected to the sentinel word (they rewrite its current value, so the
scatter needs no branch).  Cross-warp stores to the same address in one
step have an implementation-defined winner (XLA scatter with duplicate
indices) — the CUDA-race semantics the paper's race-free programs never
observe; CUDA gives no stronger guarantee either.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from .. import isa
from .state import MachineConfig, SMState
from .fetch_decode import Decoded
from .read import Operands

class Written(NamedTuple):
    regs: jnp.ndarray
    pred: jnp.ndarray
    smem: jnp.ndarray
    gmem: jnp.ndarray
    gw: jnp.ndarray


def _write_col(table: jnp.ndarray, idx: jnp.ndarray, wr: jnp.ndarray,
               val: jnp.ndarray) -> jnp.ndarray:
    """table (W, 32, R), idx (W,), wr / val (W, 32) -> ``table`` with
    ``val`` in column ``idx[w]`` of every lane where ``wr``; an index
    outside the file writes nothing."""
    cols = jnp.arange(table.shape[2], dtype=jnp.int32)
    hit = wr[..., None] & (cols == idx[:, None, None])
    return jnp.where(hit, val[..., None], table)


def write_back(cfg: MachineConfig, st: SMState, dec: Decoded,
               ops: Operands, result: jnp.ndarray,
               nib_new: jnp.ndarray) -> Written:
    G = st.gmem.shape[0] - 1

    # ---- register writeback (opcode-class bitmask test, per warp) ------
    has_dst = ((jnp.int32(isa.WRITES_REG_MASK) >> dec.op) & 1) != 0
    regs = _write_col(st.regs, dec.dst, ops.exec_mask & has_dst[:, None],
                      result)

    # ---- predicate writeback -------------------------------------------
    is_setp = dec.op == isa.ISETP
    pred = _write_col(st.pred, dec.pdst, ops.exec_mask & is_setp[:, None],
                      nib_new)

    # global / shared stores (inactive lanes write the sentinel word)
    st_g = ops.exec_mask & (dec.op == isa.STG)[:, None]
    gidx = jnp.where(st_g, ops.gaddr, G).ravel()
    gval = jnp.where(st_g, ops.s2, st.gmem[G]).ravel()
    gmem = st.gmem.at[gidx].set(gval)
    gwrt = st.gw.at[gidx].set(st.gw[gidx] | st_g.ravel())

    st_s = ops.exec_mask & (dec.op == isa.STS)[:, None]
    sidx = jnp.where(st_s, ops.saddr, cfg.smem_words).ravel()
    sval = jnp.where(st_s, ops.s2, st.smem[cfg.smem_words]).ravel()
    smem = st.smem.at[sidx].set(sval)

    return Written(regs=regs, pred=pred, smem=smem, gmem=gmem, gw=gwrt)
