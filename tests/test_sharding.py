"""Sharding rules + debug-mesh integration (no 512-device requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.launch import mesh as M
from repro.launch.steps import build_train_step
from repro.models import api
from repro.optim import OptConfig, opt_init


@pytest.fixture(scope="module")
def prod_mesh():
    # a (4, 2) stand-in mesh exercises the same rule logic on 8 "devices"
    if len(jax.devices()) >= 8:
        return M.make_mesh((4, 2), ("data", "model"))
    return M.make_mesh((1, 1), ("data", "model"))


def test_param_rules_shard_expected_axes():
    mesh = M.make_mesh((1, 1), ("data", "model"))
    assert M.param_spec("embed", (49152, 960), mesh) == P("model", None)
    assert M.param_spec("layers/attn/wq", (32, 960, 960), mesh) == \
        P(None, "data", "model")
    assert M.param_spec("layers/attn/wo", (32, 960, 960), mesh) == \
        P(None, "model", "data")
    assert M.param_spec("layers/moe/wi", (61, 384, 7168, 2048), mesh) == \
        P(None, "model", "data", None)
    assert M.param_spec("layers/ln1", (32, 960), mesh) == P()
    assert M.param_spec("final_norm", (960,), mesh) == P()


def test_param_rules_drop_nondivisible_axes():
    mesh = M.make_mesh((1, 1), ("data", "model"))
    # force axis sizes via a fake mesh dict is awkward; instead verify the
    # _fit helper directly with a production-shaped mesh mock
    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")
    # vocab 50280 % 16 != 0 -> vocab axis must not shard
    spec = M._fit(FakeMesh, (50280, 768), ("model", None))
    assert spec == P(None, None)
    spec2 = M._fit(FakeMesh, (49152, 960), ("model", None))
    assert spec2 == P("model", None)


def test_opt_state_spec_mirrors_params():
    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")
    ps = M.param_spec("layers/ffn/wi", (32, 960, 2560), FakeMesh)
    ms = M.opt_spec("m/layers/ffn/wi", (32, 960, 2560), FakeMesh)
    assert ps == ms
    # factored rows/cols keep compatible prefixes
    row = M.opt_spec("v/layers/ffn/wi/row", (32, 960), FakeMesh)
    col = M.opt_spec("v/layers/ffn/wi/col", (32, 2560), FakeMesh)
    assert row == P(None, "data")
    assert col == P(None, "model")


def test_activation_specs():
    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")
    assert M.act_spec("act_resid", (256, 4096, 960), FakeMesh) == \
        P("data", None, None)
    assert M.act_spec("act_ffn", (256, 4096, 2560), FakeMesh) == \
        P("data", None, "model")
    # 15 heads don't divide 16 -> head axis dropped
    assert M.act_spec("act_heads", (256, 4096, 15, 64), FakeMesh) == \
        P("data", None, None, None)


def test_decode_state_spec_long_context():
    class FakeMesh:
        shape = {"pod": 2, "data": 16, "model": 16}
        axis_names = ("pod", "data", "model")
    # batch=1: shard time axis; kv heads 32 shard over model
    spec = M.decode_state_spec("kv/0", (7, 1, 524288, 32, 64), FakeMesh)
    assert spec == P(None, None, ("pod", "data"), "model", None)
    # batch=128: shard batch
    spec2 = M.decode_state_spec("kv/0", (28, 128, 32768, 8, 128), FakeMesh)
    assert spec2[1] == ("pod", "data")


def test_train_step_runs_on_debug_mesh(prod_mesh):
    spec = configs.reduced(configs.get("smollm_360m"))
    opt_cfg = OptConfig(lr=1e-3)
    _, jit_for, (psh, osh) = build_train_step(spec, prod_mesh, opt_cfg,
                                              donate=False)
    with jax.set_mesh(prod_mesh):
        params = api.init(jax.random.key(0), spec)
        opt_state = opt_init(params, opt_cfg)
        # arrays made under an Auto mesh are committed replicated; the
        # step's in_shardings accept only their own placement (host
        # arrays, like the batch, are placed by the step itself)
        params, opt_state = jax.device_put((params, opt_state), (psh, osh))
        B, S = 4, 32
        batch = {"tokens": np.zeros((B, S), np.int32),
                 "labels": np.ones((B, S), np.int32)}
        step = jit_for(jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch))
        p2, o2, stats = step(params, opt_state, batch)
    assert bool(jnp.isfinite(stats["loss"]))
    # params actually changed
    delta = sum(float(jnp.sum(jnp.abs(a.astype(jnp.float32) -
                                      b.astype(jnp.float32))))
                for a, b in zip(jax.tree.leaves(params),
                                jax.tree.leaves(p2)))
    assert delta > 0


def test_dryrun_collective_parsing():
    from repro.launch import hloanalysis as H
    hlo = """
HloModule test

ENTRY %main (a: f32[16,16]) -> f32[16,16] {
  %a = f32[16,16]{1,0} parameter(0)
  %ar = f32[16,16]{1,0} all-reduce(%a), replica_groups={}, to_apply=%sum
  ROOT %ag = f32[16,16]{1,0} all-gather(%ar), dimensions={0}
}
"""
    cost = H.analyze(hlo)
    assert cost.coll_by_type["all-reduce"] == 16 * 16 * 4
    assert cost.coll_by_type["all-gather"] == 16 * 16 * 4


def test_moe_expert_decode_regime_shards_contraction():
    """§Perf M5: tiny per-group capacity (decode) shards the contracted
    D over data (weights stay put); train capacity shards groups."""
    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")
    dec = M.act_spec("moe_expert", (128, 384, 4, 7168), FakeMesh, "seq")
    assert dec == P(None, "model", None, "data")
    trn = M.act_spec("moe_expert", (2048, 384, 16, 7168), FakeMesh, "seq")
    assert trn == P("data", "model", None, None)


# ------------------------------------------------ mesh golden-spec pins

def test_fit_golden_rule_table():
    """Pin ``_fit`` over its full rule table: keep a divisible axis,
    drop a non-divisible one, keep size-1 axes (named or None), pad the
    spec to rank, and multiply tuple axes — the simplified single
    expression must produce exactly the specs the old triple-nested
    conditional did."""
    class FakeMesh:
        shape = {"data": 4, "model": 2, "one": 1}
        axis_names = ("data", "model", "one")
    cases = [
        ((8, 8), ("data", "model"), P("data", "model")),
        ((6, 8), ("data", "model"), P(None, "model")),     # 6 % 4 != 0
        ((8, 7), ("data", "model"), P("data", None)),      # 7 % 2 != 0
        ((5, 5), ("one", None), P("one", None)),           # size-1 kept
        ((8, 8, 3), ("data", "model"), P("data", "model", None)),
        ((8,), (("data", "model"),), P(("data", "model"))),  # 8 % (4*2)
        ((4,), (("data", "model"),), P(None)),             # 4 % 8 != 0
    ]
    for shape, axes, want in cases:
        assert M._fit(FakeMesh, shape, axes) == want, (shape, axes)


def test_decode_state_spec_time_axis_model_fallback():
    """Golden pin for the simplified kv arm: heads don't divide model
    but time does (and batch took the data axis), so the TIME axis
    picks up the model sharding."""
    class FakeMesh:
        shape = {"data": 4, "model": 2}
        axis_names = ("data", "model")
    spec = M.decode_state_spec("kv/0", (2, 4, 8, 3, 64), FakeMesh)
    assert spec == P(None, "data", "model", None, None)
    # heads divide -> heads shard, time stays unsharded
    spec2 = M.decode_state_spec("kv/0", (2, 4, 8, 4, 64), FakeMesh)
    assert spec2 == P(None, "data", None, "model", None)


def test_make_sm_mesh_on_forced_devices():
    """The mesh the sharded executor runs over, on 1 and (forced) 8
    devices — the constructor must produce a one-axis ("sm",)
    mesh clamped to the local device count."""
    m1 = M.make_sm_mesh(1)
    assert m1.axis_names == ("sm",) and m1.devices.size == 1
    if len(jax.devices()) >= 8:
        m8 = M.make_sm_mesh(8)
        assert m8.axis_names == ("sm",) and m8.devices.size == 8
    # over-ask clamps to the host's device count
    big = M.make_sm_mesh(10 ** 6)
    assert big.devices.size == len(jax.devices())


@pytest.mark.parametrize("n", [1, 8])
def test_mesh_factories_state_auto_axis_types(n, monkeypatch):
    """Every mesh factory builds Auto axes, on 1 and (forced) 8 devices.

    The production meshes need 256/512 chips, so their factory is
    checked on the axis types it asks ``jax.make_mesh`` for."""
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} (forced) devices")
    auto = jax.sharding.AxisType.Auto
    for mesh in (M.make_mesh((n,), ("sm",)), M.make_sm_mesh(n),
                 M.make_debug_mesh(n)):
        assert mesh.devices.size == n
        assert mesh.axis_types == (auto,) * len(mesh.axis_names)
    asked = []
    monkeypatch.setattr(jax, "make_mesh",
                        lambda shape, axes, axis_types=None, devices=None:
                        asked.append((len(axes), axis_types)))
    M.make_production_mesh()
    M.make_production_mesh(multi_pod=True)
    assert asked == [(2, (auto,) * 2), (3, (auto,) * 3)]


# ------------------------- sharded executor (8 forced host devices) ----
# conftest.py forces --xla_force_host_platform_device_count=8 before jax
# imports, so these run on any single-CPU host; the guard keeps them
# skippable when a caller overrides XLA_FLAGS.

from repro import obs                                      # noqa: E402
from repro import runtime as rt                            # noqa: E402
from repro.core import asm, isa                            # noqa: E402
from repro.launch.gpgpu_serve import (AddK,                # noqa: E402
                                      build_longtail_workload,
                                      drain_workload)

sharded8 = pytest.mark.skipif(len(jax.devices()) < 8,
                              reason="needs 8 (forced) devices")


def _conflict_kernel(base: int) -> np.ndarray:
    """Every block writes ``base + flat-block-id`` over the SAME 32
    words: position-order last-writer resolution is observable, so the
    sharded cross-device merge must reproduce it exactly."""
    p = asm.Program(f"conflict{base}")
    p.s2r("r0", isa.SR_TID)
    p.s2r("r1", isa.SR_CTA)
    p.iadd("r1", "r1", base)
    p.stg("r0", "r1", 64)
    p.exit()
    return p.finish()


def _mixed_specs(seed: int = 0):
    """Heterogeneous multi-block launches, including a write-conflict
    kernel, shared by the bit-exactness tests."""
    rng = np.random.default_rng(seed)
    specs = []
    for k, grid in [(5, (4, 1)), (9, (3, 2)), (13, (1, 1)), (21, (5, 1))]:
        mod = AddK(k, grid=grid)
        grid_bd = mod.launch()
        specs.append(rt.LaunchSpec(mod.build(), grid_bd[0], grid_bd[1],
                                   mod.make_gmem(rng)))
    specs.append(rt.LaunchSpec(_conflict_kernel(100), (7, 1), (32, 1),
                               np.zeros(128, np.int32)))
    return specs


def _assert_results_equal(a, b):
    for ra, rb in zip(a, b):
        np.testing.assert_array_equal(np.asarray(ra.gmem),
                                      np.asarray(rb.gmem))
        np.testing.assert_array_equal(ra.cycles_per_block,
                                      rb.cycles_per_block)
        np.testing.assert_array_equal(ra.op_issues, rb.op_issues)
        np.testing.assert_array_equal(ra.op_lanes, rb.op_lanes)
        assert ra.stack_ops == rb.stack_ops
        assert ra.max_sp == rb.max_sp
        assert ra.overflow == rb.overflow


@sharded8
@pytest.mark.parametrize("n_sm", [1, 2, 4, 8])
def test_sharded_execute_bit_exact(n_sm):
    """gmem + every counter bit-exact vs the single-device path, and the
    sharded runner really runs whenever a placement exists."""
    specs = _mixed_specs()
    groups0 = rt.METRICS.counter("shard.dispatch_groups").value
    base = rt.execute(specs, n_sm=n_sm, chunk=2 * n_sm, shard_sm=False)
    assert rt.METRICS.counter("shard.dispatch_groups").value == groups0
    shrd = rt.execute(specs, n_sm=n_sm, chunk=2 * n_sm, shard_sm=True)
    groups = rt.METRICS.counter("shard.dispatch_groups").value - groups0
    if n_sm == 1:
        assert groups == 0          # no multi-device placement: fallback
    else:
        assert groups > 0           # the shard_map path executed
    _assert_results_equal(base.to_results(), shrd.to_results())
    br, sr = base.report(), shrd.report()
    np.testing.assert_array_equal(br.per_sm_cycles, sr.per_sm_cycles)
    assert (br.n_steps, br.n_blocks) == (sr.n_steps, sr.n_blocks)


@sharded8
def test_sharded_conflict_last_writer_order():
    """The cross-device last-writer merge resolves overlapping writes in
    schedule-position order: the final value is the LAST block's."""
    dg = rt.execute([rt.LaunchSpec(_conflict_kernel(100), (7, 1), (32, 1),
                                   np.zeros(128, np.int32))],
                    n_sm=4, chunk=8, shard_sm=True)
    gmem = np.asarray(dg.to_results()[0].gmem)
    np.testing.assert_array_equal(gmem[64:96], np.full(32, 106))
    np.testing.assert_array_equal(gmem[:64], 0)


@sharded8
def test_sharded_per_sm_attribution_invariant():
    """Executed per-SM counters under sharding == the analytical
    round-robin replay over the global block list (placement now matches
    the ``p % n_sm`` attribution by construction)."""
    n_sm = 4
    specs = _mixed_specs()
    dg = rt.execute(specs, n_sm=n_sm, chunk=8, shard_sm=True)
    cyc = np.concatenate([np.asarray(r.cycles_per_block, np.int64)
                          for r in dg.to_results()])
    cyc += rt.BLOCK_SCHED_OVERHEAD
    want = np.bincount(np.arange(len(cyc)) % n_sm, weights=cyc,
                       minlength=n_sm).astype(np.int64)
    np.testing.assert_array_equal(dg.report().per_sm_cycles, want)


def test_shard_plan_fallbacks():
    """No placement on one SM (mesh size 1) or when n_sm doesn't divide
    over the devices; a whole-number-of-SMs-per-device split plans."""
    assert rt.shard_plan(1) is None
    n_dev = len(jax.devices())
    if n_dev >= 8:
        assert rt.shard_plan(4).devices.size == 4
        assert rt.shard_plan(8).devices.size == 8
        assert rt.shard_plan(16).devices.size == 8   # 2 SMs per device
        assert rt.shard_plan(12) is None             # 12 % 8 != 0


@sharded8
@pytest.mark.parametrize("policy", ["bucket", "balanced"])
def test_sharded_server_drain_bit_exact(policy):
    """Full serving path (drain policies, windowing, accounting) under
    ``shard_sm=True``: oracle-checked results, identical per-SM cycle
    counters, and the per-device shard gauges published."""
    work = build_longtail_workload(6)
    _, st_a, _ = drain_workload(work, n_sm=4, policy=policy)
    srv_b, st_b, _ = drain_workload(work, n_sm=4, policy=policy,
                                    shard_sm=True)
    assert st_a.n_devices == 1 and st_b.n_devices == 4
    np.testing.assert_array_equal(st_a.per_sm_cycles, st_b.per_sm_cycles)
    assert st_a.makespan_cycles == st_b.makespan_cycles
    assert st_a.busy_cycles == st_b.busy_cycles
    np.testing.assert_array_equal(st_b.device_cycles, st_b.per_sm_cycles)
    gauges = srv_b.metrics.snapshot()["gauges"]
    assert gauges["drain.shard.n_devices"] == 4
    assert gauges["drain.shard.device_skew"] >= 1.0


@sharded8
def test_sharded_resident_drain_zero_host_transfers():
    """Device-resident gmem pool stays zero-host-transfer with sharding
    on: submit adopts once, the sharded drain window moves no gmem
    across the host boundary, counters still cost one batched fetch per
    sub-batch."""
    work = build_longtail_workload(4)
    srv = rt.RuntimeServer(n_sm=4, resident_gmem=True, shard_sm=True,
                           metrics=obs.MetricsRegistry())
    assert srv.n_devices == 4
    tickets = {}
    for i, (name, mod, n, code, (grid, bd), g0) in enumerate(work):
        t = srv.submit(code, grid, bd, g0.copy(), client=f"t{i}")
        tickets[t] = (mod, n, g0)
    transfers = rt.TRANSFERS.window()
    results, stats = srv.drain()
    assert transfers.gmem_uploads == 0
    assert transfers.gmem_syncs == 0
    assert transfers.counter_syncs == stats.n_sub_batches
    assert stats.n_devices == 4
    for t, (mod, n, g0) in tickets.items():
        np.testing.assert_array_equal(
            np.asarray(results[t].gmem)[mod.out_slice(n)],
            mod.oracle(g0, n))
