"""The FlexGrip-JAX streaming multiprocessor (SM) — public facade.

The SM implementation lives in :mod:`repro.core.pipeline`, one module
per paper pipeline stage (Fetch/Decode, Read, Execute, Write, Control)
plus the seed single-warp reference interpreter.  This module keeps the
stable import surface — ``MachineConfig``, ``run_block``, the state and
counter types — so the scheduler, energy model, customization analyzer,
benchmarks and tests are agnostic to the issue discipline.

Faithful architectural features (paper §3-4):

* warps of 32 threads; ``n_sp`` scalar processors per SM arrange a warp
  into ``32 / n_sp`` rows, so one issue costs ``rows`` cycles;
* per-warp **warp stack** of ``(address, type, mask)`` entries handling
  nested divergence: SSY pushes a reconvergence entry, a divergent BRA
  pushes a taken entry and runs the not-taken path first, and the
  ``.S``-flagged reconvergence instruction pops (Fig. 2);
* 4 predicate registers per thread, each holding a 4-bit SZCO nibble
  written by ISETP; the (predicate, condition) pair indexes a lookup
  table to produce the per-thread mask bit;
* round-robin warp scheduling, block-level barriers (BAR), and a
  customizable datapath (``enable_mul``, ``num_read_operands``,
  ``warp_stack_depth`` — §4.1/4.2).

Because the program is an *input array*, one jit-compiled interpreter
executes any kernel binary of the same padded length: the overlay
property that motivates the paper.

Issue disciplines (``MachineConfig.execute_backend``):

* ``"jnp"`` — lockstep all-warp issue: every READY warp fetches,
  decodes and executes its instruction in the same ``lax.while_loop``
  iteration over a (W, 32) lane grid.  Cycle counters still charge the
  seed's serialized-issue cost, so all paper timing results are
  unchanged.
* ``"reference"`` — the seed interpreter: one round-robin warp per
  iteration; the bit-exact oracle for the lockstep path.
"""
from __future__ import annotations

from .pipeline import (  # noqa: F401  (re-exported public surface)
    EXECUTE_BACKENDS, FINISHED, READY, WAIT, Counters, MachineConfig,
    SMState, _LANES, _pack, _run_block_jit, _unpack, init_state,
    issue_one_warp, run_block, sm_step)

# Back-compat alias for the seed's private initializer name.
_init_state = init_state
