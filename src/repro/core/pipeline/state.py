"""Architectural state shared by every issue discipline.

``MachineConfig`` is the static architecture description (the paper's §4
customization axes plus our substrate knobs); ``SMState`` is the carried
loop state of the interpreter; ``Counters`` drives the energy model.
All three are consumed both by the lockstep all-warp pipeline
(:mod:`repro.core.pipeline`) and by the seed single-warp reference
interpreter (:mod:`repro.core.pipeline.reference`).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax.numpy as jnp

from .. import isa

READY, WAIT, FINISHED = 0, 1, 2

_LANES = jnp.arange(isa.WARP_SIZE, dtype=jnp.int32)

#: Issue disciplines selectable via ``MachineConfig.execute_backend``:
#:   ``"jnp"``       — the lockstep all-warp pipeline (default);
#:   ``"reference"`` — the seed one-warp-per-issue interpreter, kept as
#:                     the equivalence oracle for the lockstep step.
EXECUTE_BACKENDS = ("jnp", "reference")


@dataclasses.dataclass(frozen=True)
class MachineConfig:
    """Static architectural parameters (the customization axes of §4)."""
    n_sp: int = 8                 # scalar processors per SM (8/16/32)
    n_regs: int = 16              # 32-bit GPRs per thread
    warp_stack_depth: int = 32    # §4.1 customization axis
    enable_mul: bool = True       # §4.2: multiplier present?
    num_read_operands: int = 3    # §4.2: third read port present?
    smem_words: int = 4096        # 16 KB shared memory per SM
    mem_latency_global: int = 8   # extra cycles per global access (AXI)
    mem_latency_shared: int = 2   # extra cycles per shared access
    max_cycles: int = 4_000_000   # runaway-program guard
    execute_backend: str = "jnp"  # see EXECUTE_BACKENDS

    def __post_init__(self):
        if self.execute_backend not in EXECUTE_BACKENDS:
            raise ValueError(
                f"execute_backend must be one of {EXECUTE_BACKENDS}, "
                f"got {self.execute_backend!r}")

    @property
    def rows_per_warp(self) -> int:
        """A 32-thread warp is arranged into rows of n_sp threads."""
        return max(1, isa.WARP_SIZE // self.n_sp)

    def lut_bits(self, n_warps: int = 8) -> int:
        """LUT/FF-area proxy (paper Tables 2/6): warp-stack registers
        (66 bits/entry, Fig. 2), predicate file, per-warp control state,
        and the multiplier / third-operand-port datapaths.  The register
        file is EXCLUDED — on the FPGA it lives in block RAM, which the
        paper reports separately from LUT area.
        """
        stack = n_warps * self.warp_stack_depth * 66
        pred = n_warps * isa.WARP_SIZE * 4 * 4
        ctrl = n_warps * (32 + 32 + 2)
        # read-operand units + ALU datapath per SP lane
        read_units = self.num_read_operands * self.n_sp * 32 * 3
        mul = (self.n_sp * 32 * 24) if self.enable_mul else 0
        return stack + pred + ctrl + read_units + mul

    def state_bits(self, n_warps: int = 8) -> int:
        """Total architectural state (LUT proxy + BRAM regfile)."""
        regfile = n_warps * isa.WARP_SIZE * self.n_regs * 32
        return self.lut_bits(n_warps) + regfile


#: Slot past the opcodes in the loop's ``op_issues`` / ``op_lanes``
#: vectors.  Every warp a step visits adds one issue: to its opcode if
#: it executes, else to ``TRIP_SLOT`` (with no lanes).  So a block's
#: issues up to this slot are its loop trips times the warps per step,
#: counted by the scatter that already runs every step, with no op of
#: its own.  ``split_trips`` strips the slot, so a block's
#: :class:`Counters` hold opcodes only.
TRIP_SLOT = isa.NUM_OPCODES

#: Slots the machine loop appends to ``op_issues`` past
#: :data:`TRIP_SLOT` when it ends: the trips on which its store phase ran
#: the global and the shared scatter.  ``split_trips`` strips them too.
GSTORE_SLOT, SSTORE_SLOT = TRIP_SLOT + 1, TRIP_SLOT + 2


class Counters(NamedTuple):
    """Per-block dynamic-activity counters (drive the energy model).

    Inside the machine loop both opcode vectors carry one more entry,
    :data:`TRIP_SLOT`; the loop's ``op_issues`` comes out with two more,
    :data:`GSTORE_SLOT` and :data:`SSTORE_SLOT`."""
    op_issues: jnp.ndarray   # (NUM_OPCODES,) instruction issues per opcode
    op_lanes: jnp.ndarray    # (NUM_OPCODES,) active-lane executions per opcode
    cycles: jnp.ndarray      # SM cycles for this block
    stack_ops: jnp.ndarray   # warp-stack pushes + pops
    max_sp: jnp.ndarray      # observed maximum warp-stack depth
    overflow: jnp.ndarray    # 1 if a push ever exceeded warp_stack_depth


class SMState(NamedTuple):
    pc: jnp.ndarray          # (W,) int32
    alive: jnp.ndarray       # (W, 32) bool — thread not EXITed
    active: jnp.ndarray      # (W, 32) bool — current divergence mask
    wstate: jnp.ndarray      # (W,) int32 READY/WAIT/FINISHED
    stack_addr: jnp.ndarray  # (W, D) int32
    stack_type: jnp.ndarray  # (W, D) int32
    stack_mask: jnp.ndarray  # (W, D) uint32
    sp: jnp.ndarray          # (W,) int32
    pred: jnp.ndarray        # (W, 32, 4) int32 SZCO nibbles
    regs: jnp.ndarray        # (W, 32, R) int32
    smem: jnp.ndarray        # (S+1,) int32 (last word = store sentinel)
    gmem: jnp.ndarray        # (G+1,) int32 (last word = store sentinel)
    gw: jnp.ndarray          # (G+1,) bool — global words written by block
    last_warp: jnp.ndarray   # scalar int32 (round-robin pointer)
    counters: Counters


def _pack(mask_bool: jnp.ndarray) -> jnp.ndarray:
    """(..., 32) bool lane mask -> (...,) uint32 bitmask."""
    bits = jnp.uint32(1) << jnp.arange(isa.WARP_SIZE, dtype=jnp.uint32)
    return jnp.sum(jnp.where(mask_bool, bits, jnp.uint32(0)), axis=-1)


def _unpack(mask_u32: jnp.ndarray) -> jnp.ndarray:
    """(...,) uint32 bitmask -> (..., 32) bool lane mask."""
    lanes = jnp.arange(isa.WARP_SIZE, dtype=jnp.uint32)
    return ((mask_u32[..., None] >> lanes) & jnp.uint32(1)) != 0


def init_state(cfg: MachineConfig, n_warps: int, block_dim: int,
               gmem: jnp.ndarray) -> SMState:
    W, D, R = n_warps, cfg.warp_stack_depth, cfg.n_regs
    tid = _LANES[None, :] + 32 * jnp.arange(W, dtype=jnp.int32)[:, None]
    exists = tid < block_dim
    zero = jnp.zeros((), jnp.int32)
    counters = Counters(
        op_issues=jnp.zeros((TRIP_SLOT + 1,), jnp.int32),
        op_lanes=jnp.zeros((TRIP_SLOT + 1,), jnp.int32),
        cycles=zero, stack_ops=zero, max_sp=zero, overflow=zero)
    return SMState(
        pc=jnp.zeros((W,), jnp.int32),
        alive=exists,
        active=exists,
        wstate=jnp.where(jnp.any(exists, axis=1), READY, FINISHED)
                  .astype(jnp.int32),
        stack_addr=jnp.zeros((W, D), jnp.int32),
        stack_type=jnp.zeros((W, D), jnp.int32),
        stack_mask=jnp.zeros((W, D), jnp.uint32),
        sp=jnp.zeros((W,), jnp.int32),
        pred=jnp.zeros((W, isa.WARP_SIZE, 4), jnp.int32),
        regs=jnp.zeros((W, isa.WARP_SIZE, R), jnp.int32),
        # one extra word = store sentinel for masked-off lanes, so a
        # lockstep scatter cannot clobber a real store to the last
        # shared word by another warp in the same step
        smem=jnp.zeros((cfg.smem_words + 1,), jnp.int32),
        gmem=jnp.concatenate([gmem.astype(jnp.int32),
                              jnp.zeros((1,), jnp.int32)]),
        gw=jnp.zeros((gmem.shape[0] + 1,), bool),
        last_warp=jnp.array(W - 1, jnp.int32),
        counters=counters)
