import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: builds the
production mesh from 512 placeholder host devices, jits the train/serve
step with full shardings against ShapeDtypeStruct inputs, compiles, and
records memory analysis, FLOP/byte cost analysis, and the collective
schedule (bytes per collective op parsed from the optimized HLO).

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
"""
import argparse
import json
import re
import time
from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, SHAPES, get
from repro.models import api
from repro.optim import OptConfig, opt_init
from repro.launch import mesh as M
from repro.launch.steps import build_serve_step, build_train_step
from repro.launch import hloanalysis

# TPU v5e-ish hardware constants (per chip)
PEAK_FLOPS = 197e12        # bf16
HBM_BW = 819e9             # bytes/s
ICI_BW = 50e9              # bytes/s/link

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(text: str) -> int:
    """Sum bytes of every 'dtype[d0,d1,...]' shape literal in ``text``."""
    total = 0
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", text):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Bytes moved per collective type, from optimized-HLO result shapes."""
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(.+?)\s*(all-gather|all-reduce|"
                     r"reduce-scatter|all-to-all|collective-permute)"
                     r"(-start)?\(", s)
        if not m:
            continue
        shape_part, op = m.group(1), m.group(2)
        out[op] += _shape_bytes(shape_part)
        out["count"] += 1
    return out


def _flash_traffic_model(spec, seq, batch, kind) -> float:
    """Analytical HBM bytes of attention under the Pallas flash kernel
    (q/k/v/o streamed once; logits stay in VMEM).  Used to produce the
    kernel-adjusted memory term: raw counted attention bytes are swapped
    for this model.  Train ~3.3 passes (fwd + flash-bwd re-reads)."""
    fam = spec.family
    cfg = spec.cfg
    passes = 3.3 if kind == "train" else 1.0
    bt = 2  # bf16 on TPU
    if fam in ("dense", "moe"):
        L, H, K, dh = cfg.n_layers, cfg.n_heads, cfg.n_kv, cfg.dh
    elif fam == "vlm":
        L, H, K, dh = (cfg.lm.n_layers, cfg.lm.n_heads, cfg.lm.n_kv,
                       cfg.lm.dh)
    elif fam == "hybrid":
        L, H, K, dh = (cfg.n_apps, cfg.n_heads, cfg.n_kv,
                       cfg.d_model // cfg.n_heads)
    elif fam == "audio":
        dh = cfg.d_model // cfg.n_heads
        enc = cfg.n_layers * (2 * cfg.enc_len * cfg.n_heads * dh +
                              2 * cfg.enc_len * cfg.n_kv * dh)
        dec = cfg.n_layers * (2 * seq * cfg.n_heads * dh +
                              2 * seq * cfg.n_kv * dh +
                              2 * cfg.enc_len * cfg.n_kv * dh)
        return batch * (enc + dec) * bt * passes
    else:
        return 0.0
    per_layer = 2 * seq * H * dh + 2 * seq * K * dh
    return batch * L * per_layer * bt * passes


def input_shardings(tree, mesh, spec_fn):
    from jax.sharding import NamedSharding
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        M.spec_tree(tree, mesh, spec_fn))


def dryrun_cell(arch: str, shape_name: str, multi_pod: bool,
                opt_mode: str = "auto", donate: bool = True,
                variant: Dict = None, keep_hlo: str = None) -> Dict:
    """Lower+compile one (arch, shape, mesh) cell; returns the record.

    ``variant``: config-field overrides (e.g. {"attn_impl": "chunked"})
    applied with dataclasses.replace — the §Perf iteration knob.
    ``keep_hlo``: optional path to dump the optimized HLO text.
    """
    import dataclasses as _dc
    spec = get(arch)
    profile = (variant or {}).pop("profile", "tp") if variant else "tp"
    accum = (variant or {}).pop("accum", 1) if variant else 1
    if variant:
        cfg = spec.cfg
        lm_fields = {f.name for f in _dc.fields(type(cfg))}
        direct = {k: v for k, v in variant.items() if k in lm_fields}
        if direct:
            cfg = _dc.replace(cfg, **direct)
        if "moe_dispatch" in variant and getattr(cfg, "moe", None):
            cfg = _dc.replace(cfg, moe=_dc.replace(
                cfg.moe, dispatch=variant["moe_dispatch"]))
        if hasattr(cfg, "lm") and any(k.startswith("lm.") for k in variant):
            lmo = {k[3:]: v for k, v in variant.items()
                   if k.startswith("lm.")}
            cfg = _dc.replace(cfg, lm=_dc.replace(cfg.lm, **lmo))
        spec = _dc.replace(spec, cfg=cfg)
    reason = spec.skip_reason(shape_name)
    if reason:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": reason}
    mesh = M.make_production_mesh(multi_pod=multi_pod)
    seq, batch, kind = SHAPES[shape_name]
    t0 = time.time()

    if opt_mode == "auto":
        big = spec.cfg.param_count() > 2e10
        opt_mode = "adamw_lite" if big else "adamw"

    with jax.set_mesh(mesh):
        if kind == "train":
            opt_cfg = OptConfig(mode=opt_mode)
            _, jit_for, (psh, osh) = build_train_step(
                spec, mesh, opt_cfg, donate=donate, profile=profile,
                accum=accum)
            batch_shapes = api.input_specs(spec, shape_name)
            pshapes = api.param_shapes(spec)
            oshapes = jax.eval_shape(lambda p: opt_init(p, opt_cfg),
                                     pshapes)
            step = jit_for(batch_shapes)
            lowered = step.lower(pshapes, oshapes, batch_shapes)
        else:  # prefill (forward + KV fill, (B, S) tokens) or decode
            _, jit_for, psh = build_serve_step(spec, mesh, donate=donate,
                                               profile=profile)
            pshapes = api.param_shapes(spec)
            state_shapes = jax.eval_shape(
                lambda: api.decode_state(spec, batch, seq))
            n_tok = seq if kind == "prefill" else 1
            if spec.family == "vlm" and kind == "prefill":
                n_tok = seq - spec.cfg.n_patches
            tok = jax.ShapeDtypeStruct((batch, n_tok), jnp.int32)
            step, ssh = jit_for(state_shapes, tok)
            lowered = step.lower(pshapes, state_shapes, tok,
                                 jnp.zeros((), jnp.int32))
        compiled = lowered.compile()

    ca = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    if keep_hlo:
        with open(keep_hlo, "w") as f:
            f.write(hlo)
    # trip-count-aware analysis (XLA's HloCostAnalysis counts while bodies
    # once, so scanned-layer models under-report by ~n_layers)
    cost = hloanalysis.analyze(hlo)
    coll = dict(cost.coll_by_type or {})
    coll["count"] = cost.coll_count
    n_chips = mesh.size

    flops = float(cost.flops)
    bytes_accessed = float(cost.bytes)
    coll_total = float(cost.collective_bytes)

    # roofline terms (seconds); cost_analysis reports per-device numbers
    # for SPMD modules, so normalize per chip
    compute_t = flops / PEAK_FLOPS
    memory_t = bytes_accessed / HBM_BW
    collective_t = coll_total / ICI_BW
    # kernel-adjusted memory: attention buffers (scope "flashable_attn")
    # are replaced by the Pallas flash kernel's streamed q/k/v/o traffic
    flash_bytes = _flash_traffic_model(spec, seq, batch, kind) / mesh.size
    adj_bytes = max(bytes_accessed - float(cost.scope_bytes), 0.0) + \
        flash_bytes
    memory_t_flash = adj_bytes / HBM_BW
    collective_t_bf16 = float(cost.collective_bytes_bf16) / ICI_BW

    # useful model FLOPs: 6 * active params * tokens (train fwd+bwd) or
    # 2 * active params * tokens (decode fwd)
    n_active = spec.cfg.active_param_count()
    tokens = batch * (seq if kind in ("train", "prefill") else 1)
    mult = 6 if kind == "train" else 2
    model_flops = mult * n_active * tokens
    model_flops_per_chip = model_flops / n_chips

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "status": "ok", "kind": kind,
        "n_chips": n_chips,
        "seq": seq, "batch": batch,
        "opt_mode": opt_mode if kind in ("train", "prefill") else None,
        "params": spec.cfg.param_count(),
        "active_params": n_active,
        "hlo_flops_per_chip": flops,
        "hlo_bytes_per_chip": bytes_accessed,
        "builtin_flops": float(ca.get("flops", 0.0)),
        "builtin_bytes": float(ca.get("bytes accessed", 0.0)),
        "transcendentals_per_chip": float(cost.transcendental),
        "collective_bytes_per_chip": coll_total,
        "collectives": coll,
        "compute_t": compute_t,
        "memory_t": memory_t,
        "attn_scope_bytes": float(cost.scope_bytes),
        "flash_model_bytes": flash_bytes,
        "memory_t_flash": memory_t_flash,
        "collective_t": collective_t,
        "collective_t_bf16": collective_t_bf16,
        "dominant": max(
            (("compute", compute_t), ("memory", memory_t),
             ("collective", collective_t)), key=lambda kv: kv[1])[0],
        "model_flops_per_chip": model_flops_per_chip,
        "useful_flop_ratio": (model_flops_per_chip / flops) if flops else 0,
        "roofline_fraction": (
            model_flops_per_chip / PEAK_FLOPS /
            max(compute_t, memory_t, collective_t)
            if max(compute_t, memory_t, collective_t) > 0 else 0),
        "roofline_fraction_flash": (
            model_flops_per_chip / PEAK_FLOPS /
            max(compute_t, memory_t_flash, collective_t)
            if max(compute_t, memory_t_flash, collective_t) > 0 else 0),
        "roofline_fraction_adj": (
            model_flops_per_chip / PEAK_FLOPS /
            max(compute_t, memory_t_flash, collective_t_bf16)
            if max(compute_t, memory_t_flash, collective_t_bf16) > 0
            else 0),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "generated_code_bytes": mem.generated_code_size_in_bytes,
        },
        "variant": dict(variant or {}, profile=profile, accum=accum),
        "compile_s": round(time.time() - t0, 1),
    }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--opt", default="auto")
    ap.add_argument("--no-donate", action="store_true")
    ap.add_argument("--variant", default=None,
                    help="JSON config overrides, e.g. "
                         "'{\"profile\": \"seq\", \"remat\": \"full\"}'")
    args = ap.parse_args()
    variant = json.loads(args.variant) if args.variant else None

    archs = ([a for a in ARCH_IDS if a != "flexgrip"]
             if (args.all or not args.arch) else [args.arch])
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch.replace('-', '_').replace('.', 'p')}__{shape}__" \
                      f"{'multi' if mp else 'single'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"[skip-cached] {tag}")
                    continue
                print(f"[dryrun] {tag} ...", flush=True)
                try:
                    rec = dryrun_cell(arch, shape, mp, opt_mode=args.opt,
                                      donate=not args.no_donate,
                                      variant=dict(variant) if variant
                                      else None)
                except Exception as e:  # record failures too — they are bugs
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "multi" if mp else "single",
                           "status": "error", "error": repr(e)[:2000]}
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    extra = (f" dominant={rec['dominant']}"
                             f" roofline={rec['roofline_fraction']:.3f}"
                             f" compile={rec['compile_s']}s")
                print(f"  -> {status}{extra}", flush=True)


if __name__ == "__main__":
    main()
