import os
# a CPU-only tool: it never takes a chip, even on a machine with one
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("_REPRO_EXTRA_XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be run as its own process (the XLA_FLAGS line above precedes every
other import; jax locks the device count on first init).  Usage:

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b \
      --shape train_4k [--multi-pod] [--out results.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all  # full sweep

Per cell this prints/saves: compiled memory_analysis (proves it fits),
cost_analysis FLOPs/bytes, and the collective-traffic table parsed from
the compiled HLO -- the inputs to EXPERIMENTS.md §Roofline.
"""  # noqa: E402

import argparse
import json
import re
import sys
import time
from typing import Any, Dict

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, get_config, skip_reason
from repro.launch import shard_rules, steps
from repro.launch.mesh import make_production_mesh
from repro.models import model
from repro.models.config import ModelConfig
from repro.models.sharding import use_mesh_hints
from repro.optim import adamw

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_SHAPE_RE = re.compile(r"(f32|bf16|f16|s32|u32|s8|u8|pred|s64|f64)"
                       r"\[([0-9,]*)\]")
_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1, "s64": 8, "f64": 8}
_OP_RE = re.compile(r"= (.+?) (all-reduce|all-gather|reduce-scatter|"
                    r"all-to-all|collective-permute)(-start)?\(")
_GROUP_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
_GROUP_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


def _group_size(line: str) -> int:
    m = _GROUP_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUP_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


def collective_bytes(hlo: str) -> Dict[str, Any]:
    """Per-collective traffic from the compiled HLO.

    Compiled HLO prints operands by name only, so we take the *result*
    type(s) of each op and derive operand bytes:
      all-reduce / all-to-all / collective-permute: operand == result
      all-gather:     operand = result / group_size
      reduce-scatter: operand = result * group_size
    ``ring_wire_bytes`` estimates per-device link traffic with ring
    formulas: AR 2(g-1)/g * size, AG/RS (g-1)/g * full size, CP size.
    """
    out: Dict[str, Any] = {k: {"operand_bytes": 0, "result_bytes": 0,
                               "ring_wire_bytes": 0.0, "count": 0}
                           for k in COLLECTIVES}
    for line in hlo.splitlines():
        m = _OP_RE.search(line)
        if not m or "-done(" in line:
            continue
        rtype, kind = m.group(1), m.group(2)
        rbytes = 0
        for dm in _SHAPE_RE.finditer(rtype):
            dt, dims = dm.group(1), dm.group(2)
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            rbytes += n * _BYTES[dt]
        g = max(1, _group_size(line))
        if kind == "all-gather":
            obytes = rbytes // g
            wire = (g - 1) / g * rbytes
        elif kind == "reduce-scatter":
            obytes = rbytes * g
            wire = (g - 1) / g * obytes
        elif kind == "all-reduce":
            obytes = rbytes
            wire = 2 * (g - 1) / g * rbytes
        else:  # all-to-all, collective-permute
            obytes = rbytes
            wire = (g - 1) / g * rbytes if kind == "all-to-all" else rbytes
        rec = out[kind]
        rec["operand_bytes"] += obytes
        rec["result_bytes"] += rbytes
        rec["ring_wire_bytes"] += wire
        rec["count"] += 1
    out["total_wire_bytes"] = sum(v["ring_wire_bytes"] for v in out.values()
                                  if isinstance(v, dict))
    return out


def _scan_group(cfg: ModelConfig) -> int:
    """Layers per scan step (extrapolation unit)."""
    if cfg.family == "hybrid":
        return cfg.shared_attn_every
    if cfg.n_experts:
        return cfg.moe_layer_period
    return 1


def _cost_analysis_dict(ca) -> Dict[str, Any]:
    """``compiled.cost_analysis()`` as a dict ({} when the backend
    reports none)."""
    return ca or {}


def _lower_and_cost(cfg, shape, mesh, opt_compress,
                    microbatches: int = 1) -> Dict[str, Any]:
    """Lower+compile one configuration; return raw per-device costs."""
    rec: Dict[str, Any] = {}
    pspecs = model.param_specs(cfg)
    psh = shard_rules.param_sharding(cfg, mesh, pspecs)
    t0 = time.time()
    with mesh, use_mesh_hints(mesh):
        if shape.kind == "train":
            opt_cfg = adamw.AdamWConfig(compress_grads=opt_compress)
            ospecs = adamw.state_specs(pspecs, opt_cfg)
            osh = shard_rules.opt_state_sharding(cfg, mesh, pspecs, ospecs)
            bspecs = steps.input_specs(cfg, shape)
            bsh = shard_rules.batch_sharding(mesh, bspecs)
            fn = steps.make_train_step(cfg, opt_cfg,
                                       microbatches=microbatches,
                                       grad_shardings=psh)
            jitted = jax.jit(
                fn,
                in_shardings=(psh, osh, bsh),
                out_shardings=(NamedSharding(mesh, P()), psh, osh),
                donate_argnums=(0, 1))
            lowered = jitted.lower(pspecs, ospecs, bspecs)
            tokens = shape.global_batch * shape.seq_len
            rec["model_flops"] = cfg.model_flops(tokens, training=True)
        elif shape.kind == "prefill":
            bspecs = steps.input_specs(cfg, shape)
            bsh = shard_rules.batch_sharding(mesh, bspecs)
            fn = steps.make_prefill_step(cfg)
            jitted = jax.jit(fn, in_shardings=(psh, bsh))
            lowered = jitted.lower(pspecs, bspecs)
            tokens = shape.global_batch * shape.seq_len
            rec["model_flops"] = cfg.model_flops(tokens, training=False)
        else:  # decode
            cspecs, ispec = steps.decode_extras(cfg, shape)
            csh = shard_rules.cache_sharding(cfg, mesh, cspecs)
            bspecs = steps.input_specs(cfg, shape)
            bsh = shard_rules.batch_sharding(mesh, bspecs)
            fn = steps.make_serve_step(cfg)
            jitted = jax.jit(
                fn,
                in_shardings=(psh, csh, bsh["tokens"],
                              NamedSharding(mesh, P())),
                donate_argnums=(1,))
            lowered = jitted.lower(pspecs, cspecs, bspecs["tokens"], ispec)
            rec["model_flops"] = cfg.model_flops(shape.global_batch,
                                                 training=False)
        rec["lower_s"] = round(time.time() - t0, 2)
        t0 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 2)

    ma = compiled.memory_analysis()
    rec["memory_per_device"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
    }
    ca = _cost_analysis_dict(compiled.cost_analysis())
    rec["cost_per_device"] = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
    }
    rec["collectives"] = collective_bytes(compiled.as_text())
    return rec


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             opt_compress: bool = False,
             extrapolate: bool = True,
             microbatches: int = 1) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    reason = skip_reason(cfg, shape)
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    if reason:
        rec["skipped"] = reason
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    rec["n_devices"] = int(mesh.devices.size)
    rec["microbatches"] = microbatches
    rec.update(_lower_and_cost(cfg, shape, mesh, opt_compress,
                               microbatches))

    if extrapolate:
        # XLA's cost analysis counts a while (scan) body ONCE regardless
        # of trip count.  Two-point extrapolation recovers exact totals:
        # compile at 1 and 2 scan groups, solve body = c2 - c1,
        # outside = c1 - body, total = outside + body * n_groups.
        g = _scan_group(cfg)
        trips_full = cfg.n_layers // g
        if trips_full > 2:
            c1 = _lower_and_cost(cfg.with_(n_layers=g, unroll=True),
                                 shape, mesh, opt_compress, microbatches)
            c2 = _lower_and_cost(cfg.with_(n_layers=2 * g, unroll=True),
                                 shape, mesh, opt_compress, microbatches)

            def extrap(f1: float, f2: float) -> float:
                body = f2 - f1
                outside = f1 - body
                return outside + body * trips_full

            rec["cost_per_device_scanned"] = {
                k: extrap(c1["cost_per_device"][k], c2["cost_per_device"][k])
                for k in ("flops", "bytes_accessed")
            }
            wire = {}
            for k in COLLECTIVES:
                wire[k] = extrap(c1["collectives"][k]["ring_wire_bytes"],
                                 c2["collectives"][k]["ring_wire_bytes"])
            wire["total"] = sum(wire.values())
            rec["collective_wire_bytes_scanned"] = wire
        else:
            rec["cost_per_device_scanned"] = dict(rec["cost_per_device"])
            wire = {k: rec["collectives"][k]["ring_wire_bytes"]
                    for k in COLLECTIVES}
            wire["total"] = sum(wire.values())
            rec["collective_wire_bytes_scanned"] = wire
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="sweep every (arch, shape) on this mesh")
    ap.add_argument("--out", default=None, help="append JSONL here")
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in sorted(ARCHS):
            for s in sorted(SHAPES):
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    ok = True
    for arch, shape in cells:
        # scan-heavy families (ssm/hybrid) already fit without grad
        # accumulation, and their unrolled-microbatch extrapolation
        # compiles are prohibitively slow -- use mb=1 there
        mb = args.microbatches
        if get_config(arch).family in ("ssm", "hybrid"):
            mb = 1
        try:
            rec = run_cell(arch, shape, args.multi_pod,
                           microbatches=mb)
        except Exception as e:  # a failing cell is a bug in our system
            rec = {"arch": arch, "shape": shape,
                   "mesh": "2x16x16" if args.multi_pod else "16x16",
                   "error": f"{type(e).__name__}: {e}"}
            ok = False
        print(json.dumps(rec))
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
