"""Multi-SM executor: blocks from one or more launches, round-robin SMs.

The paper's block scheduler (§4.3) assigns thread blocks to SMs
round-robin; Table 3's 1.77–1.98× two-SM scalings follow from
``max over SMs of (sum of its blocks' cycles)``.  PR 1 replayed that sum
on the host *after* a functional run; here the schedule is **executed**:

* the global block list — the concatenation of every launch's blocks —
  is laid out position-major, so position ``p`` runs on SM ``p % n_sm``
  in super-step ``p // n_sm``;
* each dispatch runs ``steps_per_dispatch × n_sm`` positions through one
  ``vmap`` over the flattened (super-step, SM) axis — the batched SM
  axis of the issue — with a ragged tail padded by masked duplicate
  blocks so the machine compiles **once** per bucketed shape;
* per-SM cycle counters accumulate **on device** from the executed
  blocks (``sm_cyc.at[p % n_sm].add(cycles + overhead)``), replacing the
  analytical replay, which is kept as :meth:`GridResult.per_sm_cycles`
  and cross-checked in tests;
* write sets merge into each launch's global memory in position order —
  bit-exact with the seed's sequential block-order resolution, which
  CUDA-race-free kernels never observe anyway.

All array shapes are **bucketed** (code length, gmem words, launch-batch
width — see :mod:`repro.runtime.registry`), so one trace serves any mix
of tenant binaries: the overlay property at serving scale.  Global
memory never round-trips to the host between dispatches, and results
come back as a device-resident :class:`DeviceGrid` whose host
materialization is deferred until :meth:`DeviceGrid.to_results`.
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core import isa
from ..core.pipeline import Counters, MachineConfig, run_block_body, \
    split_trips
from ..obs import METRICS, TRACER, MetricsRegistry, Tracer, jit_call
from . import registry as reg
from .registry import Module, ModuleRegistry

# Cycles the block scheduler spends dispatching one block (parameter pass,
# register-file id init — §3.1 "initializes registers ... with thread IDs").
BLOCK_SCHED_OVERHEAD = 24


def _transfer(field: str) -> None:
    """Count one host<->device crossing (``transfers.<field>`` counter)."""
    METRICS.counter("transfers." + field).inc()


class TransferLog:
    """Deprecation shim: a *view* over the ``transfers.*`` registry
    counters.

    The executor's transfer counts — ``gmem_uploads`` (host arrays
    padded onto the device in :func:`_pad_gmem_device`), ``gmem_syncs``
    (per-launch gmem materializations in :meth:`DeviceGrid.to_results`
    with ``host_gmem=True``) and ``counter_syncs`` (the one batched
    accounting fetch in :meth:`DeviceGrid._host_fetch`) — now live in
    :data:`repro.obs.METRICS` as ``transfers.*`` counters.  This class
    keeps the historical ``TRANSFERS.reset(); ...; TRANSFERS.gmem_syncs``
    idiom working: each view holds a per-field baseline, ``reset()``
    re-bases the view (the underlying counters are monotone and never
    rewind), and attribute reads return *counter − baseline*.

    New code should prefer :meth:`window`, which returns an independent
    zero-based view — scoped measurement without mutating the shared
    ``TRANSFERS`` baseline other code may be relying on.
    """

    _FIELDS = ("gmem_uploads", "gmem_syncs", "counter_syncs")

    def __init__(self) -> None:
        object.__setattr__(self, "_base",
                           {f: 0 for f in self._FIELDS})
        self.reset()

    def _raw(self, field: str) -> int:
        return METRICS.counter("transfers." + field).value

    def __getattr__(self, name: str) -> int:
        if name in self._FIELDS:
            return self._raw(name) - self._base[name]
        raise AttributeError(name)

    def __setattr__(self, name: str, value) -> None:
        if name in self._FIELDS:
            # legacy direct mutation (`TRANSFERS.gmem_uploads += 1`)
            # routes the delta into the registry counter
            METRICS.counter("transfers." + name).inc(
                value - getattr(self, name))
        else:
            object.__setattr__(self, name, value)

    def reset(self) -> "TransferLog":
        """Re-base this view: all three fields read 0 until the next
        crossing.  Registry counters are untouched."""
        for f in self._FIELDS:
            self._base[f] = self._raw(f)
        return self

    def window(self) -> "TransferLog":
        """A fresh zero-based view over the same counters — the scoped
        measurement idiom (``w = TRANSFERS.window(); ...; w.gmem_syncs``)
        that cannot disturb other holders' baselines."""
        return TransferLog()

    def snapshot(self) -> dict:
        return {f: getattr(self, f) for f in self._FIELDS}


#: Process-wide transfer-counter view (see :class:`TransferLog`; the
#: counters themselves live in ``repro.obs.METRICS``).
TRANSFERS = TransferLog()

#: Launch-batch-width buckets: a drain of L concurrent launches pads its
#: per-launch arrays to the next bucket so the dispatch never retraces on
#: the number of resident tenants.
LAUNCH_BUCKETS = (1, 2, 4, 8, 16, 32)


def bucket_launches(n: int) -> int:
    return reg.bucket(n, LAUNCH_BUCKETS, 32)


class GridResult(NamedTuple):
    """Per-launch result: final memory plus the paper's activity counters.

    ``gmem`` is host numpy on the default path; under the resident
    serving mode (``DeviceGrid.to_results(host_gmem=False)``) it is a
    device array that never crossed to the host."""
    gmem: np.ndarray            # final global memory (original length)
    cycles_per_block: np.ndarray
    op_issues: np.ndarray       # (NUM_OPCODES,) int64, summed over blocks
    op_lanes: np.ndarray        # (NUM_OPCODES,) int64
    stack_ops: int
    max_sp: int
    overflow: bool

    def per_sm_cycles(self, n_sm: int) -> np.ndarray:
        """Analytical per-SM cycle totals under round-robin assignment.

        Kept as the cross-check for the *executed* counters of
        :class:`MultiSMReport`.  float64 bincount weights are exact here:
        totals stay far below 2**53.
        """
        cyc = np.asarray(self.cycles_per_block,
                         np.int64) + BLOCK_SCHED_OVERHEAD
        sm = np.arange(len(cyc)) % n_sm
        return np.bincount(sm, weights=cyc,
                           minlength=n_sm).astype(np.int64)

    def sm_cycles(self, n_sm: int) -> int:
        """Kernel time on ``n_sm`` SMs under round-robin block assignment."""
        return int(self.per_sm_cycles(n_sm).max())


class MultiSMReport(NamedTuple):
    """Executed-schedule timing: per-SM counters out of the run itself."""
    n_sm: int
    per_sm_cycles: np.ndarray   # (n_sm,) int64 — executed, not replayed
    n_steps: int                # super-steps in the executed schedule
    n_blocks: int               # real (non-padding) blocks executed
    device_gmem_words: int = 0  # words the stacked gmem allocation holds
    useful_gmem_words: int = 0  # words the launches actually asked for
    max_sp: int = 0             # warp-stack high-water mark (max over blocks)
    overflow: bool = False      # any block's warp stack overflowed

    @property
    def kernel_cycles(self) -> int:
        """Makespan of this dispatch group: the busiest SM's cycles.
        Sub-batches of a drain run back-to-back, so a drain's makespan
        is the sum of its groups' kernel_cycles — the duration the
        cost-model policies (``BalancedDrain``) minimize."""
        return int(self.per_sm_cycles.max())

    @property
    def busy_cycles(self) -> int:
        """Total SM-cycles of real work in this group (sum over SMs).
        ``busy / (n_sm * kernel_cycles)`` is the drain-level
        ``DrainStats.duration_balance``."""
        return int(self.per_sm_cycles.sum())

    @property
    def padded_gmem_words(self) -> int:
        """Memory the bucketing wasted: allocation minus requested words.

        This is the per-dispatch-group cost the drain policies minimize —
        a monolithic drain pads every tenant to the batch-wide max gmem
        bucket; bucket-keyed sub-batching keeps it near zero.
        """
        return self.device_gmem_words - self.useful_gmem_words

    @property
    def occupancy(self) -> float:
        """Fraction of SM-step slots holding a real (non-padding) block."""
        slots = self.n_steps * self.n_sm
        return self.n_blocks / slots if slots else 0.0


class LaunchSpec(NamedTuple):
    """One kernel launch: binary (or Module), geometry, global memory."""
    code: Union[np.ndarray, Module]
    grid: Tuple[int, int]
    block_dim: Union[int, Tuple[int, int]]
    gmem: object                # np.ndarray or device jnp.ndarray


def _norm_block_dim(block_dim) -> Tuple[int, int]:
    if isinstance(block_dim, tuple):
        return block_dim
    return block_dim, 1


def warps_for(block_dim) -> int:
    """Warps one block of ``block_dim`` threads occupies."""
    bdx, bdy = _norm_block_dim(block_dim)
    return -(-bdx * bdy // isa.WARP_SIZE)


def _block_positions(grid: Tuple[int, int]) -> np.ndarray:
    """(gx*gy, 2) block coordinates in the scheduler's launch order."""
    gx, gy = grid
    xs, ys = np.meshgrid(np.arange(gx), np.arange(gy))
    return np.stack([xs.ravel(), ys.ravel()], 1).astype(np.int32)


@functools.partial(jax.jit, static_argnums=(0, 1),
                   donate_argnums=(10, 11))  # gmems/sm_cyc update in place
def _run_positions(cfg: MachineConfig, n_warps: int, codes, bdims, bd_xys,
                   grid_xys, pos_launch, pos_bxy, pos_valid, sm_ids,
                   gmems, sm_cyc):
    """Execute one dispatch group of schedule positions.

    ``codes``/``bdims``/``bd_xys``/``grid_xys``/``gmems`` are stacked
    per-launch arrays (bucketed L); ``pos_*`` select each position's
    launch and block.  Blocks run under one vmap over the flattened
    (super-step, SM) axis, write sets merge in position order, and the
    per-SM cycle counters accumulate on device.  The counters come back
    for every position, padding included, still holding each
    position's loop trips (:func:`split_trips` takes them out).
    """
    def run_one(li, bxy):
        return run_block_body(cfg, n_warps, codes[li], bdims[li],
                              bd_xys[li], bxy, grid_xys[li], gmems[li])

    mem, wrt, ctr = jax.vmap(run_one)(pos_launch, pos_bxy)

    # masked scan merge: later positions overwrite earlier ones, matching
    # the sequential block-order resolution; padding positions are inert
    def merge(acc, x):
        mem_i, wrt_i, li, valid = x
        return acc.at[li].set(jnp.where(wrt_i & valid, mem_i, acc[li])), None

    with jax.named_scope("merge"):
        gmems, _ = jax.lax.scan(merge, gmems,
                                (mem, wrt, pos_launch, pos_valid))
    # per-SM accumulation in split hi/lo int32 lanes (x64 is disabled, so
    # there is no device int64): lo adds the low 16 bits, hi the rest.
    # Exact up to 2**15 blocks per SM per execute() — far beyond any
    # drain batch — where a single int32 would wrap at ~540 max-length
    # blocks.  report() recombines to int64.
    with jax.named_scope("sm_cycles"):
        cost = jnp.where(pos_valid, ctr.cycles + BLOCK_SCHED_OVERHEAD, 0)
        sm_cyc = sm_cyc.at[0, sm_ids].add(cost >> 16) \
                       .at[1, sm_ids].add(cost & 0xFFFF)
    return gmems, sm_cyc, ctr


def _pad_gmem_device(gmem, width: int) -> jnp.ndarray:
    """Pad one launch's global memory to its bucket, staying on device."""
    if not isinstance(gmem, jax.Array):
        _transfer("gmem_uploads")            # host numpy crossing over
    g = jnp.asarray(gmem, jnp.int32)
    if g.shape[0] == width:
        return g
    return jnp.concatenate(
        [g, jnp.zeros((width - g.shape[0],), jnp.int32)])


class DeviceGrid:
    """Device-resident results of an executed multi-launch schedule.

    Nothing here forces a host sync: ``launch_gmem`` returns device
    arrays (usable as the next launch's input — stream chaining), and
    JAX's async dispatch keeps the host free until ``to_results`` or
    ``report`` materialize numpy values.

    ``ctrs`` stacks every dispatch group's loop counters whole:
    padding included, each position's loop trips still in them, taken
    out by ``split`` (:func:`split_trips` of the run's config) after the
    one batched fetch.  ``groups`` holds per group ``(keep, valid,
    span)``: the group's rows of its real blocks in block order, which
    rows are real blocks, and its ``device-execute`` span.  Padding is
    stripped on the host too, so a group costs the device nothing
    beyond its run; with ``tracer`` enabled each group's span gets
    ``trips``, ``useful_steps`` and ``width``.
    """

    def __init__(self, *, gmems, ctrs: Counters, split, sm_cyc, n_sm: int,
                 n_steps: int, launch_offsets: Sequence[int],
                 launch_blocks: Sequence[int], orig_lens: Sequence[int],
                 groups: Sequence[tuple],
                 tracer: Optional[Tracer] = None):
        self._gmems = gmems              # (L_bucket, G) device
        self._ctrs = ctrs                # loop counters over positions
        self._split = split
        self._sm_cyc = sm_cyc            # (n_sm,) device
        self._groups = list(groups)      # (keep, valid, span) per group
        #: first row of each group in ``ctrs``, and the end
        self._starts = np.cumsum([0] + [len(v) for _, v, _ in groups])
        self._tracer = TRACER if tracer is None else tracer
        self.n_sm = n_sm
        self.n_steps = n_steps
        self._offsets = list(launch_offsets)
        self._blocks = list(launch_blocks)
        self._orig_lens = list(orig_lens)
        self._gmem_views: dict = {}
        self._host: Optional[tuple] = None
        self._steps: Optional[List[dict]] = None
        self._results: dict = {}

    @property
    def n_launches(self) -> int:
        return len(self._blocks)

    def launch_gmem(self, i: int) -> jnp.ndarray:
        """Launch ``i``'s final global memory — device array, no sync.

        Memoized so repeated calls (``done()`` polling, event snapshots)
        observe one dispatched array rather than re-slicing each time.
        """
        if i not in self._gmem_views:
            self._gmem_views[i] = self._gmems[i, :self._orig_lens[i]]
        return self._gmem_views[i]

    def block_until_ready(self) -> "DeviceGrid":
        jax.block_until_ready((self._gmems, self._sm_cyc))
        return self

    def _host_fetch(self) -> tuple:
        """All per-block counters plus per-SM cycles in ONE batched
        device→host transfer, memoized.  ``report`` and ``to_results``
        both draw from it, so a drain window costs exactly one
        accounting sync instead of seven scattered ``np.asarray`` hops
        (six counter leaves + the SM-cycle lanes)."""
        if self._host is None:
            _transfer("counter_syncs")
            with self._tracer.span("counter-sync", n_sm=self.n_sm,
                                   n_blocks=int(sum(self._blocks))):
                c, sm_cyc = jax.device_get((self._ctrs, self._sm_cyc))
            c, trips = self._split(c)
            # strip the padding: counter row == global block position
            rows = np.concatenate([start + keep for start, (keep, _, _)
                                   in zip(self._starts, self._groups)])
            self._host = (jax.tree.map(lambda x: x[rows], c), sm_cyc,
                          trips)
            if self._tracer.enabled:
                for (_, _, sp), steps in zip(self._groups,
                                             self.loop_steps()):
                    sp.set(**steps)
        return self._host[:2]

    def loop_steps(self) -> List[dict]:
        """Per dispatch group, from the batched fetch: ``trips``, the
        batched loop's trips (the most of any position: padded
        duplicates run too); ``useful_steps``, the trips of its valid
        positions summed; and ``width``, its positions.  A group ran
        ``trips * width`` block-slot steps, ``useful_steps`` of them
        for real blocks.  Memoized, like the fetch."""
        if self._steps is None:
            self._host_fetch()
            per_group = np.split(self._host[2], self._starts[1:-1])
            self._steps = [
                {"trips": int(t.max()), "useful_steps": int(t[valid].sum()),
                 "width": len(t)}
                for t, (_, valid, _) in zip(per_group, self._groups)]
        return self._steps

    def report(self) -> MultiSMReport:
        """Executed per-SM cycle counters (batched host fetch).

        Divergence telemetry rides along: ``max_sp`` / ``overflow``
        max-reduce over the executed blocks from the same fetch — the
        aggregation used to sum only issues/lanes/stack_ops and
        silently drop both, so a stack overflow on any block was
        invisible at the report level.
        """
        c, sm_cyc = self._host_fetch()
        hi_lo = np.asarray(sm_cyc, np.int64)
        nb = int(sum(self._blocks))
        max_sp = np.asarray(c.max_sp, np.int64)
        overflow = np.asarray(c.overflow)
        return MultiSMReport(
            n_sm=self.n_sm,
            per_sm_cycles=(hi_lo[0] << 16) + hi_lo[1],
            n_steps=self.n_steps,
            n_blocks=nb,
            device_gmem_words=int(np.prod(self._gmems.shape)),
            useful_gmem_words=int(sum(self._orig_lens)),
            max_sp=int(max_sp[:nb].max()) if nb else 0,
            overflow=bool(overflow[:nb].any()))

    def to_results(self, host_gmem: bool = True) -> List[GridResult]:
        """Materialize one :class:`GridResult` per launch.

        Counters always come from the one batched accounting fetch
        (:meth:`_host_fetch`).  With ``host_gmem=True`` (default) each
        launch's final gmem is synced to numpy; ``host_gmem=False``
        leaves the ``gmem`` fields as device arrays — the resident
        serving mode, where memory only crosses to the host at an
        explicit pool read/eviction.
        """
        if host_gmem in self._results:
            return self._results[host_gmem]
        c, _ = self._host_fetch()
        with self._tracer.span("to-results", n_launches=self.n_launches):
            return self._to_results(c, host_gmem)

    def _to_results(self, c: Counters, host_gmem: bool) -> List[GridResult]:
        cycles = np.asarray(c.cycles, np.int64)
        op_issues = np.asarray(c.op_issues, np.int64)
        op_lanes = np.asarray(c.op_lanes, np.int64)
        stack_ops = np.asarray(c.stack_ops, np.int64)
        max_sp = np.asarray(c.max_sp, np.int64)
        overflow = np.asarray(c.overflow)
        out = []
        for i, (off, nb) in enumerate(zip(self._offsets, self._blocks)):
            sl = slice(off, off + nb)
            if host_gmem:
                _transfer("gmem_syncs")
                gmem_i = np.asarray(self.launch_gmem(i))
            else:
                gmem_i = self.launch_gmem(i)
            out.append(GridResult(
                gmem=gmem_i,
                cycles_per_block=cycles[sl],
                op_issues=op_issues[sl].sum(0),
                op_lanes=op_lanes[sl].sum(0),
                stack_ops=int(stack_ops[sl].sum()),
                max_sp=int(max_sp[sl].max()) if nb else 0,
                overflow=bool(overflow[sl].any())))
        self._results[host_gmem] = out
        return out


def execute(launches: Sequence[LaunchSpec], n_sm: int = 1,
            cfg: MachineConfig = MachineConfig(), chunk: int = 8,
            pad_warps: Optional[int] = None,
            registry: Optional[ModuleRegistry] = None,
            shard_sm: bool = False, tracer: Optional[Tracer] = None,
            metrics: Optional[MetricsRegistry] = None) -> DeviceGrid:
    """Execute the blocks of ``launches`` round-robin across ``n_sm`` SMs.

    Blocks may not communicate (true of the paper's benchmarks); write
    sets merge in global block order after each dispatch.  ``chunk``
    bounds the positions per dispatch (rounded to a multiple of
    ``n_sm``); the ragged tail is padded with masked duplicates of the
    first block so every dispatch reuses one compiled machine.
    ``pad_warps`` forces the SM width (the serving path pads all tenants
    to one width); ``shard_sm`` executes each dispatch group
    device-parallel via ``shard_map`` over the SM mesh of
    :func:`repro.launch.mesh.make_sm_mesh` (see :func:`shard_plan` for
    the placement contract) — bit-exact with the single-device path,
    falling back to it when only one device exists or ``n_sm`` does not
    divide over the devices.

    Spans go to ``tracer`` and compile counts to ``metrics`` (default:
    the process globals): ``prepare`` (binaries, memory and schedule
    onto the device), then one ``device-execute`` per dispatch group
    (its schedule arrays and its enqueue; ``jit_miss`` on a compile).
    """
    if not launches:
        raise ValueError("execute() needs at least one launch")
    tracer = TRACER if tracer is None else tracer
    metrics = METRICS if metrics is None else metrics
    with tracer.span("prepare", n_launches=len(launches)):
        registry = registry or _default_registry
        mods = [registry.as_module(l.code) for l in launches]
        code_len = max(m.padded_len for m in mods)
        n_l = len(launches)
        l_bucket = bucket_launches(n_l)

        bdims = np.zeros(l_bucket, np.int32)
        bd_xys = np.zeros((l_bucket, 2), np.int32)
        grid_xys = np.ones((l_bucket, 2), np.int32)
        codes = np.zeros((l_bucket, code_len, isa.NUM_FIELDS), np.int32)
        codes[:, :, isa.F_OP] = isa.EXIT      # padding launches trap to EXIT
        orig_lens, gmem_parts = [], []
        pos_launch_l, pos_bxy_l = [], []
        offsets, nblocks = [], []
        for i, (launch, mod) in enumerate(zip(launches, mods)):
            bdx, bdy = _norm_block_dim(launch.block_dim)
            bdims[i] = bdx * bdy
            bd_xys[i] = (bdx, bdy)
            grid_xys[i] = launch.grid
            codes[i] = reg.pad_code(mod.code, code_len)
            g = launch.gmem
            orig_lens.append(int(g.shape[0]))
            gmem_parts.append(g)
            bxys = _block_positions(launch.grid)
            if len(bxys) == 0:
                raise ValueError(
                    f"launch {i} ({mod.name}) has an empty grid "
                    f"{launch.grid} (0 blocks)")
            offsets.append(sum(nblocks))
            nblocks.append(len(bxys))
            pos_launch_l.append(np.full(len(bxys), i, np.int32))
            pos_bxy_l.append(bxys)

        g_width = reg.bucket_gmem_len(max(orig_lens))
        gmems = jnp.stack(
            [_pad_gmem_device(g, g_width) for g in gmem_parts]
            + [jnp.zeros((g_width,), jnp.int32)] * (l_bucket - n_l))

        warps_needed = max(warps_for(int(b)) for b in bdims[:n_l])
        n_warps = pad_warps or warps_needed
        if n_warps < warps_needed:
            raise ValueError(
                f"pad_warps={pad_warps} < {warps_needed} warps required by "
                f"the widest launch ({int(bdims[:n_l].max())} threads) — "
                "threads beyond the padding would silently never run")
        pos_launch = np.concatenate(pos_launch_l)
        pos_bxy = np.concatenate(pos_bxy_l)
        n_blocks = len(pos_launch)
        if -(-n_blocks // n_sm) > 1 << 15:
            # the split hi/lo per-SM accumulator in _run_positions is exact
            # to 2**15 blocks per SM; beyond that the lo lane could wrap
            raise ValueError(
                f"{n_blocks} blocks on {n_sm} SMs exceeds the per-SM cycle "
                f"accumulator bound of {1 << 15} blocks/SM per execute() — "
                "split the grid across multiple execute() calls")

        # schedule: position p -> SM p % n_sm, super-step p // n_sm.  Each
        # dispatch group pads to a pow2-bucketed width with masked duplicate
        # blocks, so ragged tails and small grids together cost at most
        # log2(chunk)+1 cached traces — instead of either retracing per
        # ragged size (the seed behaviour) or simulating up to width-1
        # discarded blocks (full-width padding); waste is bounded below the
        # group's real block count.
        sm_ids_all = (np.arange(n_blocks) % n_sm).astype(np.int32)
        spd_max = max(1, chunk // n_sm)          # super-steps per dispatch

        mesh = shard_plan(n_sm) if shard_sm else None
        codes_d = jnp.asarray(codes)
        bdims_d = jnp.asarray(bdims)
        bd_xys_d = jnp.asarray(bd_xys)
        grid_xys_d = jnp.asarray(grid_xys)
        sm_cyc = jnp.zeros((2, n_sm), jnp.int32)    # (hi, lo) split lanes
        n_dev = int(mesh.devices.size) if mesh is not None else 1
        bucket = f"c{code_len}g{g_width}w{n_warps}sm{n_sm}" + \
            (f"x{n_dev}dev" if mesh is not None else "")
    outs, groups = [], []
    lo = 0
    while lo < n_blocks:
        spd = spd_max
        while spd // 2 >= -(-(n_blocks - lo) // n_sm):
            spd //= 2
        width = spd * n_sm
        take = min(width, n_blocks - lo)
        with tracer.span("device-execute", bucket=bucket, width=width,
                         n_blocks=take, n_sm=n_sm, n_devices=n_dev) as sp:
            pl = pos_launch[lo:lo + take]
            pb = pos_bxy[lo:lo + take]
            sm = sm_ids_all[lo:lo + take]
            if take < width:
                pad = width - take
                pl = np.concatenate([pl, np.zeros(pad, np.int32)])
                pb = np.concatenate([pb, np.zeros((pad, 2), np.int32)])
                sm = np.concatenate([sm, np.zeros(pad, np.int32)])
            valid = np.arange(width) < take
            if mesh is not None:
                # device-parallel dispatch: permute the group to SM-major
                # order so P("sm") places each SM's blocks (and counter)
                # on its owning device — placement matches the p % n_sm
                # attribution by construction
                perm = _sm_major_perm(width, n_sm)
                inv = np.argsort(perm)
                runner = _sharded_run_positions(cfg, n_warps, mesh, n_sm,
                                                spd)
                valid = valid[perm]
                group = (jnp.asarray(pl[perm]), jnp.asarray(pb[perm]),
                         jnp.asarray(valid),
                         jnp.asarray(perm.astype(np.int32)))
                metrics.counter("shard.dispatch_groups").inc()
                with jit_call("executor.run_positions_sharded", runner,
                              bucket=bucket,
                              key=(cfg, n_warps, l_bucket, code_len, g_width,
                                   width, n_sm, n_dev),
                              metrics=metrics, span=sp):
                    gmems, sm_cyc, ctr = runner(
                        codes_d, bdims_d, bd_xys_d, grid_xys_d, *group,
                        gmems, sm_cyc)
                # the slot-sharded rows of the real blocks, in global
                # block-position order
                keep = inv[:take]
            else:
                group = (jnp.asarray(pl), jnp.asarray(pb),
                         jnp.asarray(valid), jnp.asarray(sm))
                with jit_call("executor.run_positions", _run_positions,
                              bucket=bucket,
                              key=(cfg, n_warps, l_bucket, code_len, g_width,
                                   width, n_sm),
                              metrics=metrics, span=sp):
                    gmems, sm_cyc, ctr = _run_positions(
                        cfg, n_warps, codes_d, bdims_d, bd_xys_d,
                        grid_xys_d, *group, gmems, sm_cyc)
                keep = np.arange(take)
        outs.append(ctr)
        groups.append((keep, valid, sp))
        lo += take

    ctrs = jax.tree.map(lambda *xs: jnp.concatenate(xs), *outs) \
        if len(outs) > 1 else outs[0]
    return DeviceGrid(gmems=gmems, ctrs=ctrs,
                      split=functools.partial(split_trips, cfg, n_warps),
                      sm_cyc=sm_cyc, n_sm=n_sm, n_steps=-(-n_blocks // n_sm),
                      launch_offsets=offsets, launch_blocks=nblocks,
                      orig_lens=orig_lens, groups=groups, tracer=tracer)


def shard_plan(n_sm: int):
    """The SM mesh the sharded executor path will run over, or ``None``
    when sharding is inactive (single local device, or ``n_sm`` not
    divisible by the device count — each device must own a whole number
    of SMs for placement to match attribution).

    **Placement contract** (the fix for the old contiguous-placement /
    strided-attribution mismatch): schedule position ``p`` is attributed
    to SM ``p % n_sm``, and under sharding device ``d`` owns the
    *contiguous SM range* ``[d * n_sm/n_dev, (d+1) * n_sm/n_dev)`` — so
    each dispatch group is permuted to SM-major order before placement
    and every SM's blocks, and its cycle counter, live on exactly one
    device.  Per-SM counter accumulation is device-local with one psum
    reduction; no cross-device counter traffic.
    """
    from ..launch.mesh import make_sm_mesh
    mesh = make_sm_mesh(n_sm)
    n_dev = mesh.devices.size
    if n_dev <= 1 or n_sm % n_dev:
        return None
    return mesh


def _sm_major_perm(width: int, n_sm: int) -> np.ndarray:
    """Permutation from SM-major slot ``q`` to schedule position ``p``.

    ``q = s * spd + j  ->  p = j * n_sm + s`` (``spd`` super-steps per
    dispatch): SM ``s``'s blocks become contiguous, so a ``P("sm")``
    sharding of the slot axis puts each SM's blocks on its owning
    device.  ``np.argsort`` of this is the inverse (position -> slot).
    """
    spd = width // n_sm
    return np.arange(width).reshape(spd, n_sm).T.ravel()


@functools.lru_cache(maxsize=None)
def _sharded_run_positions(cfg: MachineConfig, n_warps: int, mesh, n_sm: int,
                           spd: int):
    """Build + jit one sharded dispatch: ``shard_map`` over the SM mesh.

    Device-parallel block execution with the single-device semantics
    preserved bit-exactly:

    * each device vmaps only the SM-major slots of the SMs it owns;
    * global memory merges by **last writer in schedule-position order**
      — each device scans its local blocks tracking (max writing
      position, value) per word, then a ``pmax``/``psum`` pair picks the
      globally latest write, exactly reproducing the unsharded scan
      merge (positions are unique, so the psum sums one winner);
    * per-SM cycle counters accumulate on the owning device into the
      split hi/lo lanes and reduce psum-style into the replicated
      ``(2, n_sm)`` accumulator;
    * per-block counters come back sharded along the slot axis; the
      caller gathers them back to schedule order via the inverse
      permutation, so :class:`DeviceGrid` bookkeeping is unchanged.
    """
    from jax.sharding import PartitionSpec as P
    n_dev = mesh.devices.size
    sm_per_dev = n_sm // n_dev
    local_w = sm_per_dev * spd

    def body(codes, bdims, bd_xys, grid_xys, pos_launch, pos_bxy,
             pos_valid, pos_ids, gmems, sm_cyc):
        def run_one(li, bxy):
            return run_block_body(cfg, n_warps, codes[li], bdims[li],
                                  bd_xys[li], bxy, grid_xys[li], gmems[li])

        mem, wrt, ctr = jax.vmap(run_one)(pos_launch, pos_bxy)

        # device-local last-writer merge: track, per (launch, word), the
        # highest schedule position that wrote and its value
        last0 = jnp.full(gmems.shape, -1, jnp.int32)
        val0 = jnp.zeros_like(gmems)

        def merge(carry, x):
            last, val = carry
            mem_i, wrt_i, li, valid, pid = x
            newer = wrt_i & valid & (pid > last[li])
            return (last.at[li].set(jnp.where(newer, pid, last[li])),
                    val.at[li].set(jnp.where(newer, mem_i, val[li]))), None

        with jax.named_scope("merge"):
            (last, val), _ = jax.lax.scan(
                merge, (last0, val0), (mem, wrt, pos_launch, pos_valid,
                                       pos_ids))
            # cross-device combine: the device holding the globally
            # latest write wins; everyone else contributes 0 to the psum
            gmax = jax.lax.pmax(last, "sm")
            win = jnp.where((last == gmax) & (gmax >= 0), val, 0)
            gmems = jnp.where(gmax >= 0, jax.lax.psum(win, "sm"), gmems)

        # per-SM counters: slots q of local SM k map to global SM
        # (device * sm_per_dev + k) — accumulation never leaves the
        # owning device; one tiny psum folds the per-device partials
        with jax.named_scope("sm_cycles"):
            sm0 = jax.lax.axis_index("sm") * sm_per_dev
            local_sm = sm0 + jnp.arange(local_w, dtype=jnp.int32) // spd
            cost = jnp.where(pos_valid, ctr.cycles + BLOCK_SCHED_OVERHEAD,
                             0)
            contrib = jnp.zeros((2, n_sm), jnp.int32) \
                .at[0, local_sm].add(cost >> 16) \
                .at[1, local_sm].add(cost & 0xFFFF)
            sm_cyc = sm_cyc + jax.lax.psum(contrib, "sm")
        return gmems, sm_cyc, ctr

    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(), P(), P("sm"), P("sm"), P("sm"), P("sm"),
                  P(), P()),
        out_specs=(P(), P(), P("sm")),
        check_vma=False)
    return jax.jit(sharded, donate_argnums=(8, 9))


#: Registry behind bare execute()/run_grid() calls.  Bounded so a
#: long-lived process streaming fresh binaries through the
#: compatibility path (e.g. generated test programs) cannot grow it
#: monotonically; serving layers hold their own registries.
_default_registry = ModuleRegistry(max_modules=1024)


def run_grid(code, grid: Tuple[int, int], block_dim, gmem,
             cfg: MachineConfig = MachineConfig(), chunk: int = 8,
             n_sm: int = 1) -> GridResult:
    """Single-launch compatibility entry: execute and materialize."""
    dg = execute([LaunchSpec(code, grid, block_dim, gmem)],
                 n_sm=n_sm, cfg=cfg, chunk=chunk)
    return dg.to_results()[0]
