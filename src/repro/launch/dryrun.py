import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count="
    + os.environ.get("REPRO_DRYRUN_DEVICES", "512"))
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay the first statements in this module: jax
locks the device count at first init, and only the dry-run fakes 512
host devices (tests/benches keep the real single device).

Per cell this emits a JSON record with:
  - compiled.memory_analysis()  (per-device bytes: args/temp/output)
  - compiled.cost_analysis()    (per-device HLO FLOPs + bytes accessed)
  - the collective schedule parsed from post-SPMD HLO (op type, result
    bytes, group size, estimated per-device link bytes)
  - the three §Roofline terms for TPU v5e constants
Failures (sharding mismatch, OOM-at-compile, unsupported collective) are
system bugs per the brief — surfaced, not swallowed.
"""  # noqa: E402
import argparse
import dataclasses
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax
import numpy as np

from repro.configs import (ALIASES, ARCHS, SHAPES, get_config,
                           get_smoke_config, shape_applicable)
from repro.launch import partition as pt
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.launch.steps import (abstract_cache, abstract_opt,
                                abstract_params, input_structs,
                                make_decode_step, make_prefill_step,
                                make_train_step)
from repro.optim import AdamWConfig

# --- TPU v5e roofline constants (per chip) ---------------------------------
PEAK_FLOPS = 197e12      # bf16
HBM_BW = 819e9           # B/s
LINK_BW = 50e9           # B/s per ICI link

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
                "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4,
                "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s+(\([^)]*\)|\S+)\s+(all-gather|all-reduce|reduce-scatter"
    r"|all-to-all|collective-permute)")
_SHAPE_RE = re.compile(r"(pred|[sufbc]\d?\d+)\[([\d,]*)\]")
_GROUP_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUP_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(txt: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(txt):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def parse_collectives(hlo: str, n_devices: int):
    """Collective schedule: per-op result bytes + est. link bytes/device."""
    out = []
    for line in hlo.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        result, op = m.group(1), m.group(2)
        rb = _shape_bytes(result)
        gm = _GROUP_IOTA_RE.search(line)
        if gm:
            p = int(gm.group(2))
        else:
            gm2 = _GROUP_RE.search(line)
            p = len(gm2.group(1).split(",")) if gm2 else n_devices
        p = max(p, 2)
        if op == "all-gather":
            link = rb * (p - 1) / p
        elif op == "reduce-scatter":
            link = rb * (p - 1)            # result is the scattered shape
        elif op == "all-reduce":
            link = 2 * rb * (p - 1) / p
        elif op == "all-to-all":
            link = rb * (p - 1) / p
        else:                               # collective-permute
            link = rb
        out.append({"op": op, "result_bytes": rb, "group": p,
                    "link_bytes": link})
    return out


def _probe_layers(cfg):
    """(l1_cfg, l2_cfg, var_layers_in_l1, full_var_layers) for the
    unrolled cost probes.  XLA's cost_analysis counts while-loop bodies
    once, so per-layer FLOPs/bytes/collectives are measured by compiling
    unrolled 1- and 2-variable-layer models and differencing; totals are
    extrapolated linearly (exact: layers are homogeneous by
    construction)."""
    f = {"unroll_layers": True, "q_chunk": 1 << 30, "remat": cfg.remat}
    if cfg.family == "moe":
        nd = cfg.first_dense_layers
        c1 = dataclasses.replace(cfg, n_layers=nd + 1, **f)
        c2 = dataclasses.replace(cfg, n_layers=nd + 2, **f)
        return c1, c2, 1, cfg.n_layers - nd
    if cfg.family == "hybrid":
        per = cfg.attn_every
        c1 = dataclasses.replace(cfg, n_layers=per, **f)
        c2 = dataclasses.replace(cfg, n_layers=2 * per, **f)
        return c1, c2, 1, cfg.n_layers // per
    if cfg.family == "audio":
        c1 = dataclasses.replace(cfg, n_layers=1, enc_layers=1, **f)
        c2 = dataclasses.replace(cfg, n_layers=2, enc_layers=2, **f)
        return c1, c2, 1, cfg.n_layers
    c1 = dataclasses.replace(cfg, n_layers=1, **f)
    c2 = dataclasses.replace(cfg, n_layers=2, **f)
    return c1, c2, 1, cfg.n_layers


def _compile_cell(cfg, spec, mesh):
    """Lower + compile one cell; returns (compiled, n_devices)."""
    pstruct = abstract_params(cfg)
    pspecs = pt.sanitize_tree(mesh, pt.param_specs(pstruct), pstruct)
    batch_struct = input_structs(cfg, spec)
    bspecs = pt.sanitize_tree(mesh, pt.batch_specs(mesh, batch_struct),
                              batch_struct)
    if spec.kind == "train":
        ostruct = abstract_opt(cfg)
        ospecs = pt.opt_specs(ostruct, pspecs)
        fn = make_train_step(cfg, AdamWConfig())
        in_specs = (pspecs, ospecs, bspecs)
        out_specs = (pspecs, ospecs,
                     jax.tree.map(lambda _: pt.P(),
                                  {"loss": 0, "ce": 0, "aux": 0,
                                   "grad_norm": 0, "lr": 0}))
        args = (pstruct, ostruct, batch_struct)
        donate = (0, 1)
    else:
        cstruct = abstract_cache(cfg, spec)
        seq_shard = spec.global_batch == 1
        cspecs = pt.sanitize_tree(
            mesh, pt.cache_specs(mesh, cstruct, batch=spec.global_batch,
                                 seq_shard=seq_shard), cstruct)
        if spec.kind == "prefill":
            fn = make_prefill_step(cfg)
        else:
            fn = make_decode_step(cfg)
        logits_spec = pt.P(pt.batch_dims(mesh)
                           if spec.global_batch > 1 else None, None)
        in_specs = (pspecs, cspecs, bspecs)
        out_specs = (logits_spec, cspecs)
        args = (pstruct, cstruct, batch_struct)
        donate = (1,)
    with jax.set_mesh(mesh):
        jitted = jax.jit(fn,
                         in_shardings=pt.named(mesh, in_specs),
                         out_shardings=pt.named(mesh, out_specs),
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    return compiled


_CONV_RE = re.compile(r"= f32\[([\d,]+)\][^=]*convert\(")


def bf16_ghost_bytes(hlo: str) -> int:
    """CPU-backend artifact: XLA CPU legalizes bf16 by upconversion and
    materializes whole-tensor f32 copies of large bf16 buffers (e.g. the
    layer-scan residual stack).  Verified absent from the jaxpr (the
    residual is bf16 at the JAX level) — a real TPU backend computes
    bf16 natively.  Count: f32 convert outputs ≥64 MiB whose exact shape
    also exists as a bf16 tensor.  Reported so the v5e memory estimate
    can be corrected (memory.peak_tpu_estimate)."""
    bf16_shapes = set(re.findall(r"bf16\[([\d,]+)\]", hlo))
    seen = {}
    for m in _CONV_RE.finditer(hlo):
        dims = m.group(1)
        if dims not in bf16_shapes:
            continue
        n = 1
        for d in dims.split(","):
            n *= int(d)
        if n * 4 >= 64 * 1024 * 1024:
            seen[dims] = n * 4
    return int(sum(seen.values()))


def _cost_record(compiled, n_dev):
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):   # older JAX wraps the dict per device
        ca = ca[0] if ca else {}
    colls = parse_collectives(compiled.as_text(), n_dev)
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll_bytes": float(sum(c["link_bytes"] for c in colls)),
        "colls": colls,
    }


def run_cell(arch: str, shape: str, multi_pod: bool,
             smoke: bool = False, overrides: dict | None = None) -> dict:
    spec = SHAPES[shape]
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    ok, why = shape_applicable(arch, shape)
    if not ok:
        return {"arch": arch, "shape": shape,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}

    if smoke:  # selftest: tiny mesh, same axis names
        shape_ax = ((2, 2, 4), ("pod", "data", "model")) if multi_pod \
            else ((4, 4), ("data", "model"))
        mesh = make_mesh(shape_ax[0], shape_ax[1])
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(np.prod(mesh.devices.shape))
    t0 = time.time()

    # -- main compile (full model, scan-over-layers) ------------------------
    compiled = _compile_cell(cfg, spec, mesh)
    t_compile = time.time() - t0
    ma = compiled.memory_analysis()
    ghost = bf16_ghost_bytes(compiled.as_text())
    main_cost = _cost_record(compiled, n_dev)

    # -- cost probes: unrolled 1- and 2-layer compiles + extrapolation ------
    t1 = time.time()
    c1, c2, l1, l_full = _probe_layers(cfg)
    r1 = _cost_record(_compile_cell(c1, spec, mesh), n_dev)
    r2 = _cost_record(_compile_cell(c2, spec, mesh), n_dev)
    t_probe = time.time() - t1

    # grad-accum microbatch scan is itself a while loop counted once by
    # cost_analysis — scale costs back up by k (train cells only)
    k_accum = cfg.grad_accum if spec.kind == "train" else 1

    def extrap(key):
        per = (r2[key] - r1[key]) * k_accum
        return max(r1[key] * k_accum + (l_full - l1) * per, 0.0), per

    flops, flops_per_layer = extrap("flops")
    bytes_acc, _ = extrap("bytes")
    coll_bytes, coll_per_layer = extrap("coll_bytes")

    n_par = cfg.n_params()
    active = n_par
    if cfg.family == "moe":
        dead = (cfg.n_experts - cfg.top_k) * 3 * cfg.d_model * \
            cfg.moe_d_ff * (cfg.n_layers - cfg.first_dense_layers)
        active = n_par - dead
    tokens = spec.global_batch * (spec.seq_len if spec.kind != "decode"
                                  else 1)
    mult = 6 if spec.kind == "train" else 2
    model_flops = mult * active * tokens / n_dev

    colls = main_cost["colls"]
    rec = {
        "arch": arch, "shape": shape,
        "mesh": "multi" if multi_pod else "single",
        "n_devices": n_dev, "status": "ok",
        "compile_s": round(t_compile, 1), "probe_s": round(t_probe, 1),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_estimate": (ma.argument_size_in_bytes
                              + ma.temp_size_in_bytes
                              + ma.output_size_in_bytes
                              - ma.alias_size_in_bytes),
            "cpu_bf16_ghost_bytes": ghost,
            # clamped at the argument-residency floor: the ghost detector
            # can over-count when an f32 convert output aliases/fuses
            "peak_tpu_estimate": max(
                ma.argument_size_in_bytes + ma.temp_size_in_bytes
                + ma.output_size_in_bytes - ma.alias_size_in_bytes
                - ghost,
                ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes),
        },
        "cost": {
            "flops_per_device": flops,
            "bytes_accessed_per_device": bytes_acc,
            "flops_per_layer": flops_per_layer,
            "raw_scan_flops_per_device": main_cost["flops"],
            "probe_note": ("flops/bytes/collectives extrapolated from "
                           "unrolled 1/2-layer probe compiles (XLA cost "
                           "analysis counts while-loop bodies once)"),
        },
        "collectives": {
            "count": len(colls),
            "by_op": {op: int(sum(1 for c in colls if c["op"] == op))
                      for op in set(c["op"] for c in colls)},
            "link_bytes_per_device": coll_bytes,
            "link_bytes_per_layer": coll_per_layer,
            "schedule_sample": colls[:40],
        },
        "roofline": {
            "compute_s": flops / PEAK_FLOPS,
            "memory_s": bytes_acc / HBM_BW,
            "collective_s": coll_bytes / LINK_BW,
            "model_flops_per_device": model_flops,
            "useful_flops_ratio": (model_flops / flops) if flops else None,
        },
    }
    dom = max(("compute_s", "memory_s", "collective_s"),
              key=lambda k: rec["roofline"][k])
    rec["roofline"]["dominant"] = dom
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--smoke", action="store_true",
                    help="use reduced configs (selftest)")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg override key=value (perf variants), "
                         "e.g. --override mla_absorb=False")
    ap.add_argument("--tag", default="",
                    help="suffix for output filenames (variants)")
    args = ap.parse_args()

    overrides = {}
    for kv in args.override:
        k, v = kv.split("=", 1)
        overrides[k] = {"True": True, "False": False}.get(
            v, int(v) if v.lstrip("-").isdigit() else v)

    archs = ARCHS if args.arch == "all" else [
        ALIASES.get(args.arch, args.arch)]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape}_{'multi' if mp else 'single'}"
                if args.tag:
                    tag += f"_{args.tag}"
                path = outdir / f"{tag}.json"
                if path.exists():
                    print(f"[skip existing] {tag}")
                    continue
                print(f"[run] {tag}", flush=True)
                try:
                    rec = run_cell(arch, shape, mp, smoke=args.smoke,
                                   overrides=overrides)
                except Exception as e:  # noqa: BLE001
                    failures += 1
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "multi" if mp else "single",
                           "status": "error", "error": repr(e),
                           "trace": traceback.format_exc()[-4000:]}
                path.write_text(json.dumps(rec, indent=1))
                st = rec.get("status")
                extra = ""
                if st == "ok":
                    r = rec["roofline"]
                    extra = (f" dom={r['dominant']}"
                             f" c={r['compute_s']:.2e}"
                             f" m={r['memory_s']:.2e}"
                             f" n={r['collective_s']:.2e}"
                             f" compile={rec['compile_s']}s")
                print(f"[done] {tag}: {st}{extra}", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
