"""Production mesh + sharding rules.

Mesh: ``(data=16, model=16)`` per pod (256 chips, TPU v5e-256-like) and
``(pod=2, data=16, model=16)`` for the 2-pod, 512-chip dry-run.  The
``pod`` axis composes with ``data`` as an outer batch axis; gradient
reduction over it crosses DCN, which is where the int8-compression path
and the hierarchical-reduce hillclimb live (EXPERIMENTS.md §Perf).

Sharding rules are *name- and shape-driven*: ``param_spec`` pattern-
matches tree paths (wq/wo/wi/experts/embed/...), and every rule degrades
gracefully — an axis that does not divide evenly is dropped from the
spec rather than failing, so one rule set serves all ten architectures
(15-head smollm and 24-head mamba included).

The paper connection (DESIGN.md §4): the FlexGrip block scheduler maps
thread blocks round-robin onto SMs; here data shards map round-robin
onto chips along ``(pod, data)``.  ``core/scheduler.py`` implements the
SM-level original; this module is the same policy at fleet scale.
"""
from __future__ import annotations

import re
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def make_mesh(shape, axes, devices=None) -> Mesh:
    """The one mesh constructor: every axis is ``AxisType.Auto``.

    JAX's own ``make_mesh`` defaults to Explicit axes, under which a
    gather from a sharded array outside ``shard_map`` and a
    ``with_sharding_constraint`` on an unsharded intermediate are type
    errors; the runtime and the model code rely on the compiler placing
    those.  Every mesh in the repo — the factories below and the
    tests' — goes through here so they all state the same types.
    ``devices`` defaults to the local ones; a compile check passes the
    devices of a described topology.
    """
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_devices: int = 1) -> Mesh:
    """Tiny mesh over real local devices for tests."""
    return make_mesh((1, n_devices), ("data", "model"))


def make_sm_mesh(n_sm: int) -> Mesh:
    """One-axis ``("sm",)`` mesh for the device runtime's block executor.

    The paper's blocks→SMs round-robin, lifted to devices: the runtime's
    schedule axis shards over up to ``n_sm`` local devices (fewer when
    the host has fewer — a single-device host degenerates to a no-op
    placement, which is still the same policy).
    """
    n = min(max(1, n_sm), len(jax.devices()))
    return make_mesh((n,), ("sm",))


def batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def _fit(mesh: Mesh, shape, spec_axes) -> P:
    """Drop sharding on axes whose size does not divide evenly."""
    fixed = []
    for dim, axis in zip(shape, spec_axes):
        n = _axis_size(mesh, axis)
        fixed.append(axis if dim % n == 0 else None)
    # pad spec to rank
    fixed += [None] * (len(shape) - len(fixed))
    return P(*fixed)


# --------------------------------------------------------------- params
_PARAM_RULES = (
    # (path regex, spec builder given (shape, batch, mesh))
    (r"(embed|lm_head)$", lambda s: ("model", None)),
    (r"enc_pos$", lambda s: (None, None)),
    (r"vision_proj$", lambda s: (None, "model")),
    (r"(wq|wk|wv)$", lambda s: ("data", "model")),
    (r"attn/wo$|self/wo$|cross/wo$|shared.*wo$", lambda s: ("model", "data")),
    (r"(wi|wg)$", lambda s: ("data", "model")),       # ffn in-projections
    (r"ffn/wo$", lambda s: ("model", "data")),
    (r"router$", lambda s: ("data", "model")),
    (r"in_proj$", lambda s: ("data", "model")),
    (r"conv_w$", lambda s: (None, "model")),
    (r"out_proj$", lambda s: ("model", "data")),
    (r"moe/(wi|wg)$", lambda s: ("model", "data", None)),
    (r"moe/wo$", lambda s: ("model", None, "data")),
)


def param_spec(path: str, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Sharding spec for one parameter leaf (path uses '/')."""
    # layer-stacked params carry a leading L (or n_apps) axis: unsharded
    lead = ()
    core = shape
    stacked = bool(re.search(r"(layers|enc|dec)/", path)) and len(shape) >= 2
    if stacked:
        lead, core = (None,), shape[1:]
    # MoE expert tensors: (L, E, D, F)
    if re.search(r"moe/(wi|wg)$", path) and len(core) == 3:
        return _fit(mesh, shape, lead + ("model", "data", None))
    if re.search(r"moe/wo$", path) and len(core) == 3:
        return _fit(mesh, shape, lead + ("model", None, "data"))
    for pat, rule in _PARAM_RULES:
        if re.search(pat, path):
            axes = rule(core)
            if len(axes) != len(core):
                axes = tuple(axes) + (None,) * (len(core) - len(axes))
            return _fit(mesh, shape, lead + tuple(axes[:len(core)]))
    return P()  # norms, biases, scalars: replicated


def spec_tree(tree, mesh: Mesh, spec_fn):
    """Map (path, leaf shape) -> PartitionSpec over a pytree."""
    flat, tdef = jax.tree_util.tree_flatten_with_path(tree)
    specs = []
    for path, leaf in flat:
        name = "/".join(
            str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        specs.append(spec_fn(name, tuple(leaf.shape), mesh))
    return jax.tree_util.tree_unflatten(tdef, specs)


def param_sharding_tree(shapes_tree, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        spec_tree(shapes_tree, mesh, param_spec))


def opt_spec(path: str, shape, mesh: Mesh) -> P:
    """Optimizer state mirrors its parameter's sharding.

    Factored second moments (…/v/…/row, …/col) inherit the parameter
    spec minus the reduced axis; the step counter is replicated.
    """
    if path.endswith("step"):
        return P()
    core = re.sub(r"^(m|v)/", "", path)
    is_row = core.endswith("/row")
    is_col = core.endswith("/col")
    core = re.sub(r"/(row|col)$", "", core)
    def padded(base, n):
        t = tuple(base)
        return t + (None,) * (n - len(t))

    if is_row:
        base = padded(param_spec(core, shape + (1,), mesh), len(shape) + 1)
        return P(*base[:len(shape)])
    if is_col:
        # col drops the second-to-last param axis
        base = padded(param_spec(core, shape[:-1] + (1, shape[-1]), mesh),
                      len(shape) + 1)
        return P(*(base[:len(shape) - 1] + (base[-1],)))
    return param_spec(core, shape, mesh)


# ----------------------------------------------------------- activations
def act_spec(kind: str, shape, mesh: Mesh, profile: str = "tp") -> Optional[P]:
    """Activation sharding.

    ``profile="tp"``  — Megatron-style tensor parallelism: hidden/head
    axes shard over ``model``; each layer pays two (B, S, D) activation
    all-reduces (the psum after wo / ffn-wo).

    ``profile="seq"`` — sequence parallelism (beyond-paper, §Perf): the
    SEQUENCE axis shards over ``model`` end-to-end; weight contractions
    are local (weights FSDP-gathered, far fewer bytes than activations)
    and attention gathers only the GQA K/V heads.  Eliminates the
    per-layer activation all-reduces entirely.
    """
    # weight tensors constrained inside layer bodies: "param:<name>".
    # The transpose of this constraint pins the per-layer weight-grad
    # cotangent to the same sharding, steering SPMD to reduce-scatter
    # gradients inside the scan loop instead of full all-reduce.  Only
    # active in the optimized "seq" profile — the "tp" baseline keeps
    # XLA's default placement (paper-faithful measurement).
    if kind.startswith("param:"):
        if profile != "seq":
            return None
        return param_spec("layers/" + kind[6:], shape, mesh)
    b = batch_axes(mesh)
    bspec = b if len(b) > 1 else b[0]
    if profile == "seq":
        if kind in ("act_resid", "act_ffn"):
            return _fit(mesh, shape, (bspec, "model", None))
        if kind == "act_heads":               # q: S-sharded
            return _fit(mesh, shape, (bspec, "model", None, None))
        if kind == "act_kv":                  # k/v: gathered (GQA: small)
            return _fit(mesh, shape, (bspec, None, None, None))
        if kind == "moe_expert" and len(shape) == 4:
            G, E, C, D = shape
            if C <= 8:
                # decode regime (minimal per-group capacity): token
                # parallelism is worthless; shard the CONTRACTED D over
                # data instead so the expert matmul psums small (C, F)
                # partials rather than all-gathering the FSDP-sharded
                # expert weights every token (§Perf M5)
                return _fit(mesh, shape, (None, "model", None, "data"))
            return _fit(mesh, shape, (bspec, "model", None, None))
        return None
    if kind == "act_resid":
        return _fit(mesh, shape, (bspec, None, None))
    if kind == "act_ffn":
        return _fit(mesh, shape, (bspec, None, "model"))
    if kind in ("act_heads", "act_kv"):
        return _fit(mesh, shape, (bspec, None, "model", None))
    if kind == "moe_expert":              # (G, E, C, D)
        return _fit(mesh, shape, (bspec, "model", None, None))
    return None


def make_constrain(mesh: Optional[Mesh], profile: str = "tp"):
    """Build the ``constrain(x, kind)`` callback passed into models."""
    if mesh is None:
        return lambda x, *a: x

    def constrain(x, kind):
        spec = act_spec(kind, x.shape, mesh, profile)
        if spec is None:
            return x
        # batch axis must divide too (e.g. batch=1 long-context decode)
        sizes = [_axis_size(mesh, a) for a in spec]
        ok = all(d % n == 0 for d, n in zip(x.shape, sizes))
        if not ok:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return constrain


# ------------------------------------------------------------ batch/state
def batch_spec(path: str, shape, mesh: Mesh) -> P:
    """Input batches: leading dim is the global batch."""
    b = batch_axes(mesh)
    bspec = b if len(b) > 1 else b[0]
    return _fit(mesh, shape, (bspec,) + (None,) * (len(shape) - 1))


def decode_state_spec(path: str, shape, mesh: Mesh) -> P:
    """Decode state: KV caches (L, B, T, K, dh), SSD states, conv states.

    Prefer sharding batch over (pod, data); if batch doesn't divide
    (long-context batch=1), shard the time axis instead.  Heads/channels
    shard over model when divisible.
    """
    b = batch_axes(mesh)
    bspec = b if len(b) > 1 else b[0]
    nb = _axis_size(mesh, b if len(b) > 1 else b[0])
    nm = mesh.shape["model"]
    if "kv" in path and len(shape) == 5:
        L, B, T, K, dh = shape
        spec = [None] * 5
        if B % nb == 0:
            spec[1] = bspec
        elif T % nb == 0:
            spec[2] = bspec
        if K % nm == 0:
            spec[3] = "model"
        elif T % nm == 0 and spec[2] is None:
            spec[2] = "model"
        return _fit(mesh, shape, tuple(spec))
    if "cross" in path and len(shape) == 5:
        L, B, T, K, dh = shape
        spec = [None, bspec if B % nb == 0 else None, None,
                "model" if K % nm == 0 else None, None]
        return _fit(mesh, shape, tuple(spec))
    if "ssm" in path and len(shape) == 5:   # (L, B, H, P, N)
        L, B, H, Pd, N = shape
        spec = [None, bspec if B % nb == 0 else None,
                "model" if H % nm == 0 else None, None, None]
        return _fit(mesh, shape, tuple(spec))
    if "conv" in path and len(shape) == 4:  # (L, B, K-1, C)
        L, B, K1, C = shape
        spec = [None, bspec if B % nb == 0 else None, None,
                "model" if C % nm == 0 else None]
        return _fit(mesh, shape, tuple(spec))
    return batch_spec(path, shape, mesh)
