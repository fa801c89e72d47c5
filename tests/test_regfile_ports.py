"""Register-file and predicate-file ports of the lockstep step.

``read_operands`` reads and ``write_back`` writes the per-thread
register file (W, 32, R) and predicate file (W, 32, 4) by a one-hot
select over their minor axis.  This module keeps the gather / scatter
formulation the step used before as the oracle and holds both stages to
it bit for bit: on random states and decoded instructions, at every
warp count the suite uses, at 16 and 32 registers, with the register
and predicate indices at their first and last columns, with no lane
writing, with guarded-off lanes, and with ISETP next to other ops.
Each case also runs under ``jax.vmap`` over a dispatch group of width
2, as the executor calls the step.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import isa
from repro.core.pipeline import MachineConfig, init_state
from repro.core.pipeline.fetch_decode import Decoded
from repro.core.pipeline.read import read_operands
from repro.core.pipeline.write import write_back

N_PRED = 4
GMEM_WORDS = 64
LUT = jnp.asarray(isa.COND_LUT)
GEOM = (jnp.array([64, 1], jnp.int32), jnp.array([1, 0], jnp.int32),
        jnp.array([2, 1], jnp.int32))

#: ISETP next to register writers, stores and ops that write nothing
OPS = (isa.ISETP, isa.MOV, isa.IADD, isa.LDG, isa.STG, isa.STS, isa.BRA,
       isa.EXIT)
CASES = ("mixed", "edges", "all_isetp", "no_writes", "guarded_off")


# ---- the oracle: the gather / scatter formulation ------------------------

def _gather_col(table, idx):
    return jnp.take_along_axis(table, idx[:, None, None], axis=2)[..., 0]


def _scatter_col(table, idx, wr, val):
    W = table.shape[0]
    rows = jnp.arange(W, dtype=jnp.int32)[:, None]
    lanes = jnp.arange(isa.WARP_SIZE, dtype=jnp.int32)[None, :]
    new = jnp.where(wr, val, _gather_col(table, idx))
    return table.at[rows, lanes, idx[:, None]].set(new)


def oracle_read(cfg, st, dec, ops):
    """``ops`` with the register and predicate reads done by gathers."""
    nib = _gather_col(st.pred, dec.gpred)
    cond_val = LUT[dec.gcond[:, None], nib]
    gm = jnp.where(dec.guarded[:, None], cond_val, True)
    exec_mask = dec.active & st.alive & gm & dec.exec_this[:, None]
    imm = dec.imm[:, None]
    s1 = jnp.where((dec.flags[:, None] & isa.FLAG_SRC1_IMM) != 0, imm,
                   _gather_col(st.regs, dec.src1))
    s2 = jnp.where((dec.flags[:, None] & isa.FLAG_SRC2_IMM) != 0, imm,
                   _gather_col(st.regs, dec.src2))
    s3 = _gather_col(st.regs, dec.src3) if cfg.num_read_operands >= 3 \
        else jnp.zeros_like(s1)
    return ops._replace(cond_val=cond_val, exec_mask=exec_mask, s1=s1,
                        s2=s2, s3=s3)


def oracle_write(st, dec, ops, result, nib_new):
    """(regs, pred) written by scatters."""
    has_dst = ((jnp.int32(isa.WRITES_REG_MASK) >> dec.op) & 1) != 0
    regs = _scatter_col(st.regs, dec.dst, ops.exec_mask & has_dst[:, None],
                        result)
    is_setp = dec.op == isa.ISETP
    pred = _scatter_col(st.pred, dec.pdst, ops.exec_mask & is_setp[:, None],
                        nib_new)
    return regs, pred


# ---- random states and decoded instructions ------------------------------

def _draw(rng, case, W, R, k):
    """The ``k``-th (config, state, decoded bundle, result, nib_new) of
    ``case``; k = 0..3 puts every edge column under both kinds of write
    at every warp count."""
    cfg = MachineConfig(n_regs=R)
    st = init_state(cfg, W, W * isa.WARP_SIZE,
                    jnp.asarray(rng.integers(-99, 99, GMEM_WORDS,
                                             dtype=np.int32)))
    i32 = np.iinfo(np.int32)
    lanes = (W, isa.WARP_SIZE)
    st = st._replace(
        regs=jnp.asarray(rng.integers(i32.min, i32.max, (W, 32, R),
                                      dtype=np.int32, endpoint=True)),
        pred=jnp.asarray(rng.integers(0, 16, (W, 32, N_PRED),
                                      dtype=np.int32)),
        alive=jnp.asarray(rng.random(lanes) < 0.9))

    def col(n):
        return rng.integers(0, n, W).astype(np.int32)

    def alternate(a, b, shift=k):
        return np.where((np.arange(W) + shift) % 2 == 0, a, b
                        ).astype(np.int32)

    op = np.asarray(OPS, np.int32)[rng.integers(0, len(OPS), W)]
    dst, pdst, gpred = col(R), col(N_PRED), col(N_PRED)
    srcs = [col(R) for _ in range(3)]
    active = rng.random(lanes) < 0.8
    exec_this = rng.random(W) < 0.9
    guarded = rng.random(W) < 0.5
    gcond = col(LUT.shape[0])
    flags = (np.where(rng.random(W) < 0.3, isa.FLAG_SRC2_IMM, 0)
             | np.where(rng.random(W) < 0.2, isa.FLAG_SRC1_IMM, 0))
    if case == "edges":
        op = alternate(isa.IADD, isa.ISETP, k // 2)
        dst, pdst, gpred = (alternate(0, R - 1), alternate(0, N_PRED - 1),
                            alternate(N_PRED - 1, 0))
        srcs = [alternate(0, R - 1), alternate(R - 1, 0),
                alternate(0, R - 1)]
        flags = np.zeros(W, np.int32)
        exec_this[:] = True
    elif case == "all_isetp":
        op[:] = isa.ISETP
    elif case == "no_writes":
        active[:] = False
    elif case == "guarded_off":
        guarded[:] = True
        exec_this[:] = True
    imm = rng.integers(-1000, 1000, W).astype(np.int32)
    dec = Decoded(
        issued=jnp.ones(W, bool), wstate=jnp.zeros(W, jnp.int32),
        op=jnp.asarray(op), dst=jnp.asarray(dst),
        src1=jnp.asarray(srcs[0]), src2=jnp.asarray(srcs[1]),
        src3=jnp.asarray(srcs[2]), imm=jnp.asarray(imm),
        flags=jnp.asarray(flags.astype(np.int32)), gpred=jnp.asarray(gpred),
        gcond=jnp.asarray(gcond), pdst=jnp.asarray(pdst),
        guarded=jnp.asarray(guarded), active=jnp.asarray(active),
        sp=jnp.zeros(W, jnp.int32), exec_this=jnp.asarray(exec_this),
        pop_taken=jnp.zeros(W, bool), do_pop=jnp.zeros(W, bool),
        top_addr=jnp.zeros(W, jnp.int32))
    result = jnp.asarray(rng.integers(i32.min, i32.max, lanes,
                                      dtype=np.int32, endpoint=True))
    nib_new = jnp.asarray(rng.integers(0, 16, lanes, dtype=np.int32))
    return cfg, st, dec, result, nib_new


@functools.partial(jax.jit, static_argnums=0)
def _ports(cfg, st, dec, result, nib_new):
    """The stages under test, chained as the step chains them."""
    ops = read_operands(cfg, LUT, *GEOM, st, dec)
    wb = write_back(cfg, st, dec, ops, result, nib_new)
    return ops, wb


@functools.partial(jax.jit, static_argnums=0)
def _oracle(cfg, st, dec, result, nib_new):
    ops = oracle_read(cfg, st, dec, read_operands(cfg, LUT, *GEOM, st, dec))
    return ops, oracle_write(st, dec, ops, result, nib_new)


def _assert_equal(got, want, tag):
    ops, wb = got
    ops_o, (regs_o, pred_o) = want
    for name in ("cond_val", "exec_mask", "s1", "s2", "s3"):
        np.testing.assert_array_equal(getattr(ops, name),
                                      getattr(ops_o, name),
                                      err_msg=f"{tag}: {name}")
    np.testing.assert_array_equal(wb.regs, regs_o, err_msg=f"{tag}: regs")
    np.testing.assert_array_equal(wb.pred, pred_o, err_msg=f"{tag}: pred")


@pytest.mark.parametrize("batched", [False, True], ids=["plain", "vmap"])
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("n_regs", [16, 32])
@pytest.mark.parametrize("n_warps", [1, 2, 8])
def test_ports_match_gather_scatter(n_warps, n_regs, case, batched):
    rng = np.random.default_rng(
        [n_warps, n_regs, CASES.index(case), int(batched)])
    draws = [_draw(rng, case, n_warps, n_regs, k) for k in range(4)]
    cfg = draws[0][0]
    if batched:
        for pair in (draws[:2], draws[2:]):
            group = jax.tree.map(lambda *a: jnp.stack(a),
                                 *[d[1:] for d in pair])
            got = jax.vmap(functools.partial(_ports, cfg))(*group)
            for i, d in enumerate(pair):
                _assert_equal(jax.tree.map(lambda a: a[i], got),
                              _oracle(*d), f"group slot {i}")
    else:
        for k, d in enumerate(draws):
            _assert_equal(_ports(*d), _oracle(*d), f"draw {k}")

    # each case exercises what it names
    outs = [_ports(*d) for d in draws]
    regs_changed = any(not np.array_equal(wb.regs, d[1].regs)
                       for (_, wb), d in zip(outs, draws))
    pred_changed = any(not np.array_equal(wb.pred, d[1].pred)
                       for (_, wb), d in zip(outs, draws))
    if case == "no_writes":
        assert not any(np.asarray(ops.exec_mask).any() for ops, _ in outs)
        assert not regs_changed and not pred_changed
    elif case == "guarded_off":
        off = [~np.asarray(ops.cond_val) & np.asarray(d[2].active)
               & np.asarray(d[1].alive) for (ops, _), d in zip(outs, draws)]
        assert any(o.any() for o in off)
        assert not any(np.asarray(ops.exec_mask)[o].any()
                       for (ops, _), o in zip(outs, off))
    elif case == "all_isetp":
        assert pred_changed and not regs_changed
    elif case == "edges":
        assert regs_changed and pred_changed
    else:
        assert regs_changed or pred_changed
