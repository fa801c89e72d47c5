"""Loop trips of the SM step, and one tracer per server down to it.

* A block's trip count (``split_trips`` of ``run_block_body``'s loop
  counters) is the number of steps a plain Python loop takes until the
  machine loop's condition is false, under both issue disciplines.
* A dispatch group's ``trips`` / ``useful_steps`` / ``width`` come from
  the one batched counter fetch, padded duplicates included, on the
  single-device and the sharded path.
* A server-local tracer sees the executor's spans under ``dispatch``,
  and the trip count adds no host<->device transfer.
* Program spans reach a JAX profile as ``repro.<name>`` annotations on
  the profiler's clock, and only while the tracer is on.
"""
import functools
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro import runtime as rt
from repro.core import isa
from repro.core.pipeline import (TRIP_SLOT, MachineConfig, block_running,
                                 init_state, run_block_body, split_trips,
                                 step_fn)
from repro.core.programs import ALL
from repro.runtime import executor as ex

REPO = Path(__file__).resolve().parents[1]


def _program(name, n=32, seed=0):
    mod = ALL[name]
    grid, bd = mod.launch(n)
    gmem = mod.make_gmem(np.random.default_rng(seed), n)
    return mod.build(n), grid, bd, gmem


def _block_args(code, grid, bd, gmem, bxy=(0, 0)):
    bdx, bdy = bd if isinstance(bd, tuple) else (bd, 1)
    return (jnp.asarray(code, jnp.int32), bdx * bdy,
            jnp.asarray([bdx, bdy], jnp.int32), jnp.asarray(bxy, jnp.int32),
            jnp.asarray(grid, jnp.int32), jnp.asarray(gmem, jnp.int32))


def _python_loop_steps(cfg, n_warps, code, bdim, bd_xy, bxy, gxy, gmem):
    """Steps a Python loop calls until the loop condition is false, and
    the state it ends in."""
    step = jax.jit(functools.partial(step_fn(cfg), cfg))
    running = jax.jit(functools.partial(block_running, cfg))
    lut = jnp.asarray(isa.COND_LUT)
    st = init_state(cfg, n_warps, bdim, gmem)
    n = 0
    while bool(running(st)):
        st = step(code, lut, bd_xy, bxy, gxy, st)
        n += 1
    return n, st


@functools.partial(jax.jit, static_argnums=(0, 1))
def _run_body(cfg, n_warps, *args):
    """One block through the machine loop: memory, written mask,
    ``Counters`` and trips."""
    mem, wrt, ctr = run_block_body(cfg, n_warps, *args)
    return (mem, wrt, *split_trips(cfg, n_warps, ctr))


#: sizes that give each program's block two warps
TWO_WARPS = {"bitonic": 64, "reduction": 128, "autocorr": 64}


@pytest.mark.parametrize("backend", ["jnp", "reference"])
@pytest.mark.parametrize("name", ["bitonic", "reduction", "autocorr"])
def test_block_trips_equal_python_loop_steps(name, backend):
    """Two warps a block, so the two disciplines visit different numbers
    of warps a step."""
    cfg = MachineConfig(execute_backend=backend)
    args = _block_args(*_program(name, TWO_WARPS[name]))
    n_warps = -(-args[1] // isa.WARP_SIZE)
    want, st = _python_loop_steps(cfg, n_warps, *args)
    mem, wrt, ctr, trips = _run_body(cfg, n_warps, *args)
    assert want > 0 and int(trips) == want
    # every warp a step visits adds one issue: opcodes or TRIP_SLOT;
    # the lockstep step visits every warp, the reference one
    warps_per_step = 1 if backend == "reference" else n_warps
    assert n_warps == 2
    assert int(st.counters.op_issues.sum()) == warps_per_step * want
    # the slot is stripped: the block's counters hold opcodes only
    assert ctr.op_issues.shape == (isa.NUM_OPCODES,)
    assert ctr.op_lanes.shape == (isa.NUM_OPCODES,)
    np.testing.assert_array_equal(ctr.op_issues,
                                  st.counters.op_issues[:TRIP_SLOT])
    np.testing.assert_array_equal(ctr.op_lanes,
                                  st.counters.op_lanes[:TRIP_SLOT])
    np.testing.assert_array_equal(mem, st.gmem[:-1])


def _block_trips(code, grid, bd, gmem, n_warps, bxy):
    args = _block_args(code, grid, bd, gmem, bxy)
    return int(_run_body(MachineConfig(), n_warps, *args)[3])


@pytest.mark.parametrize("shard_sm", [False, True])
def test_group_accounting_counts_padded_duplicate(shard_sm):
    """bitonic (1 block) then transpose (4 blocks) on 2 SMs, 2 positions
    a group: the last group holds transpose's last block and a padded
    duplicate of bitonic's block, which runs longer."""
    bit, tr = _program("bitonic"), _program("transpose")
    specs = [rt.LaunchSpec(*bit), rt.LaunchSpec(*tr)]
    n_warps = 8                          # transpose's 16x16 threads
    t_bit = _block_trips(*bit, n_warps, (0, 0))
    t_tr = [_block_trips(*tr, n_warps, (x, y))
            for y in range(2) for x in range(2)]
    assert t_bit > max(t_tr)             # the padding sets the last trip
    tracer = obs.Tracer().start()
    w = rt.TRANSFERS.window()
    dg = ex.execute(specs, n_sm=2, chunk=2, shard_sm=shard_sm,
                    tracer=tracer)
    dg.to_results()
    want = [{"trips": max(t_bit, t_tr[0]), "useful_steps": t_bit + t_tr[0],
             "width": 2},
            {"trips": max(t_tr[1], t_tr[2]),
             "useful_steps": t_tr[1] + t_tr[2], "width": 2},
            {"trips": t_bit, "useful_steps": t_tr[3], "width": 2}]
    assert dg.loop_steps() == want
    groups = tracer.find("device-execute")[::-1]      # find() is LIFO
    assert [{k: g.attrs[k] for k in want[0]} for g in groups] == want
    assert w.counter_syncs == 1


@pytest.mark.parametrize("shard_sm", [False, True])
def test_group_counts_are_the_launch_counters(shard_sm):
    """A group's ``winstr`` / ``lanes`` / ``stack_ops`` are its real
    blocks' counters from the same fetch: over the groups they sum to
    the launches' executed issues, active lanes and stack operations
    (padded duplicates left out), and reach the group spans and the
    ``simt.*`` counters.  CSR SpMV's divergent row loop makes stack
    traffic and idle lanes."""
    from repro.compiler.kernels import spmv_csr
    n = 256
    csr = (spmv_csr.build(n), *spmv_csr.launch(n),
           spmv_csr.make_gmem(np.random.default_rng(1), n))
    specs = [rt.LaunchSpec(*_program("bitonic")), rt.LaunchSpec(*csr)]
    tracer, m = obs.Tracer().start(), obs.MetricsRegistry()
    res = ex.execute(specs, n_sm=2, chunk=2, shard_sm=shard_sm,
                     tracer=tracer, metrics=m).to_results()
    want = {"winstr": sum(int(r.op_issues.sum()) for r in res),
            "lanes": sum(int(r.op_lanes.sum()) for r in res),
            "stack_ops": sum(r.stack_ops for r in res)}
    assert want["stack_ops"] > 0 and want["lanes"] < 32 * want["winstr"]
    assert {k: m.counter("simt." + k).value for k in want} == want
    groups = tracer.find("device-execute")
    assert len(groups) == 2
    assert {k: sum(g.attrs[k] for g in groups) for k in want} == want


def test_server_tracer_sees_the_executor_under_dispatch():
    code, grid, bd, g0 = _program("bitonic")
    tracer, m = obs.Tracer(), obs.MetricsRegistry()
    srv = rt.RuntimeServer(n_sm=2, tracer=tracer, metrics=m)
    glob = obs.TRACER.start()
    try:
        tracer.start()
        srv.submit(code, grid, bd, g0.copy(), client="t0")
        srv.drain()
        tracer.stop()
    finally:
        glob.stop()
    assert not glob.find("dispatch") and not glob.find("device-execute")
    glob.clear()
    (disp,) = tracer.find("dispatch")
    kids = [c.name for c in disp.children]
    n_groups = kids.count("device-execute")
    assert n_groups >= 1
    assert kids == (["prepare"] + ["device-execute"] * n_groups
                    + ["counter-sync", "to-results"])
    for c in disp.children:
        assert disp.t0 <= c.t0 <= c.t1 <= disp.t1
    groups = [c for c in disp.children if c.name == "device-execute"]
    for k in ("trips", "useful_steps", "width"):
        assert disp.attrs[k] == sum(g.attrs[k] for g in groups) > 0
    # the compile counts landed in the server's registry
    assert m.counter("jit.calls.executor.run_positions").value == n_groups


def test_jit_miss_marks_the_group_span():
    code, grid, bd, g0 = _program("reduction")
    tracer = obs.Tracer().start()
    ex._run_positions.clear_cache()
    for _ in range(2):
        ex.execute([rt.LaunchSpec(code, grid, bd, g0.copy())], n_sm=2,
                   chunk=2, tracer=tracer).to_results()
    first, second = tracer.find("device-execute")[::-1]
    assert first.attrs.get("jit_miss") is True
    assert "jit_miss" not in second.attrs


@pytest.mark.parametrize("traced", [False, True])
def test_counter_syncs_one_per_sub_batch(traced):
    code, grid, bd, g0 = _program("autocorr")
    tracer = obs.Tracer(enabled=traced)
    srv = rt.RuntimeServer(n_sm=2, tracer=tracer,
                           metrics=obs.MetricsRegistry())
    for i in range(3):
        srv.submit(code, grid, bd, g0.copy(), client=f"t{i}")
    w = rt.TRANSFERS.window()
    _, stats = srv.drain()
    assert w.counter_syncs == stats.n_sub_batches >= 1
    assert bool(tracer.find("dispatch")) == traced


def _profiled_drain(log_dir, traced):
    """One drain under the JAX profiler; returns the tracer, the host
    instant of its zero and that of the ``bench.mark`` annotation."""
    code, grid, bd, g0 = _program("reduction")
    tracer = obs.Tracer()
    srv = rt.RuntimeServer(n_sm=2, tracer=tracer,
                           metrics=obs.MetricsRegistry())
    srv.submit(code, grid, bd, g0.copy(), client="warm")
    srv.drain()                                  # compile before tracing
    jax.profiler.start_trace(str(log_dir))
    try:
        with jax.profiler.TraceAnnotation("bench.mark"):
            mark = time.perf_counter()
        base = time.perf_counter()
        if traced:
            tracer.start()
        for i in range(2):
            srv.submit(code, grid, bd, g0.copy(), client=f"t{i}")
            srv.drain()
        tracer.stop()
    finally:
        jax.profiler.stop_trace()
    return tracer, base, mark


def _repro_events(log_dir):
    import glob
    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in pd.planes for line in plane.lines
            for ev in line.events if ev.name.startswith("repro.")]


def test_profiler_shows_program_spans_on_its_clock(tmp_path):
    sys.path.insert(0, str(REPO))
    from bench import trace_reduce
    tracer, base, mark = _profiled_drain(tmp_path / "on", traced=True)
    events = _repro_events(tmp_path / "on")
    names = {n for n, _, _ in events}
    assert {"repro.drain", "repro.dispatch", "repro.prepare",
            "repro.device-execute", "repro.counter-sync",
            "repro.to-results"} <= names
    assert not any(n.startswith("repro.bench") for n in names)
    to_ns = trace_reduce.clock(
        trace_reduce.extract(str(tmp_path / "on"), host_ops=True), mark)
    spans = sorted(tracer.find("dispatch"), key=lambda s: s.t0)
    marks = sorted((s, e) for n, s, e in events if n == "repro.dispatch")
    assert len(spans) == len(marks) == 2
    for sp, (s, e) in zip(spans, marks):
        assert abs(to_ns(base + sp.t0) - s) < 1e6
        assert abs(to_ns(base + sp.t1) - e) < 1e6

    _profiled_drain(tmp_path / "off", traced=False)
    assert _repro_events(tmp_path / "off") == []
