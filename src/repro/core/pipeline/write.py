"""Write stage of the all-warp pipeline.

Commits one lockstep issue for every warp at once.  The register file
and the predicate file are written by a one-hot select over their minor
axis: each (warp, lane) writes at most one column of its own row
(``dst`` / ``pdst`` per warp), so the write is one dense ``where`` over
the (W, 32, R) file, with no scatter.  A TPU scatter pays for every
update, the select only for the file's words, which at these sizes is
the cheaper of the two at every warp count.

Global and shared stores are not written here: :func:`write_back`
returns them as one :class:`Store` request a port, and the machine
loop's store phase applies each with :func:`store_global` /
:func:`store_shared` — one scatter a port, run only on a trip where
some lane stores to it.  Inactive lanes are redirected to the sentinel
word and rewrite its current value, so a scatter over a batch of blocks
is exact for the blocks that do not store.  Cross-warp stores to the
same address in one step have an implementation-defined winner (XLA
scatter with duplicate indices) — the CUDA-race semantics the paper's
race-free programs never observe; CUDA gives no stronger guarantee
either.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from .. import isa
from .state import MachineConfig, SMState
from .fetch_decode import Decoded
from .read import Operands

class Store(NamedTuple):
    """One memory port's stores of a trip, per (warp, lane)."""
    mask: jnp.ndarray   # (W, 32) bool — lanes that store
    addr: jnp.ndarray   # (W, 32) int32 — clipped word address
    val: jnp.ndarray    # (W, 32) int32


class Written(NamedTuple):
    regs: jnp.ndarray
    pred: jnp.ndarray
    glob: Store
    shared: Store


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
    # ---- register writeback (opcode-class bitmask test, per warp) ------
    has_dst = ((jnp.int32(isa.WRITES_REG_MASK) >> dec.op) & 1) != 0
    regs = _write_col(st.regs, dec.dst, ops.exec_mask & has_dst[:, None],
                      result)

    # ---- predicate writeback -------------------------------------------
    is_setp = dec.op == isa.ISETP
    pred = _write_col(st.pred, dec.pdst, ops.exec_mask & is_setp[:, None],
                      nib_new)

    # global / shared store requests, applied by the store phase
    st_g = ops.exec_mask & (dec.op == isa.STG)[:, None]
    st_s = ops.exec_mask & (dec.op == isa.STS)[:, None]
    return Written(regs=regs, pred=pred,
                   glob=Store(st_g, ops.gaddr, ops.s2),
                   shared=Store(st_s, ops.saddr, ops.s2))


def store_global(gmem: jnp.ndarray, gw: jnp.ndarray, req: Store):
    """``(gmem, gw)`` after one trip's global stores: a scatter into
    memory and one into the written mask, each lane that does not store
    rewriting the sentinel word with its own value."""
    G = gmem.shape[0] - 1
    gidx = jnp.where(req.mask, req.addr, G).ravel()
    gval = jnp.where(req.mask, req.val, gmem[G]).ravel()
    return (gmem.at[gidx].set(gval),
            gw.at[gidx].set(gw[gidx] | req.mask.ravel()))


def store_shared(smem: jnp.ndarray, req: Store) -> jnp.ndarray:
    """``smem`` after one trip's shared stores (sentinel as above)."""
    S = smem.shape[0] - 1
    sidx = jnp.where(req.mask, req.addr, S).ravel()
    sval = jnp.where(req.mask, req.val, smem[S]).ravel()
    return smem.at[sidx].set(sval)
