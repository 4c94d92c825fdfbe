"""HLO hot-spot inspector: rank ops in a compiled dry-run cell.

    PYTHONPATH=src python -m benchmarks.hlo_top --arch qwen2-72b \
        --shape train_4k --kind all-gather --top 10

Compiles the cell at 1 scan-group (unrolled) so per-layer ops are
visible, then ranks ops of ``--kind`` (a collective, or "fusion" for
memory traffic) by result bytes, printing the JAX source metadata --
this is the "profile" of the dry-run perf loop (EXPERIMENTS.md §Perf).
"""
import os
# a CPU-only tool: it never takes a chip, even on a machine with one
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse  # noqa: E402
import re  # noqa: E402
from collections import defaultdict  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--kind", default="all-gather")
    ap.add_argument("--top", type=int, default=12)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--groups", type=int, default=1,
                    help="scan groups to unroll")
    args = ap.parse_args()

    from repro.configs import SHAPES, get_config
    from repro.launch.dryrun import _SHAPE_RE, _BYTES, _scan_group
    from repro.launch.mesh import make_production_mesh

    cfg = get_config(args.arch)
    g = _scan_group(cfg)
    cfg = cfg.with_(n_layers=args.groups * g, unroll=True)
    shape = SHAPES[args.shape]
    mesh = make_production_mesh(multi_pod=args.multi_pod)

    # reuse the lowering path but keep the compiled text
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch import shard_rules, steps
    from repro.models import model
    from repro.models.sharding import use_mesh_hints
    from repro.optim import adamw

    pspecs = model.param_specs(cfg)
    psh = shard_rules.param_sharding(cfg, mesh, pspecs)
    with mesh, use_mesh_hints(mesh):
        if shape.kind == "train":
            opt_cfg = adamw.AdamWConfig()
            ospecs = adamw.state_specs(pspecs, opt_cfg)
            osh = shard_rules.opt_state_sharding(cfg, mesh, pspecs, ospecs)
            bspecs = steps.input_specs(cfg, shape)
            bsh = shard_rules.batch_sharding(mesh, bspecs)
            fn = steps.make_train_step(cfg, opt_cfg)
            lowered = jax.jit(fn, in_shardings=(psh, osh, bsh),
                              out_shardings=(NamedSharding(mesh, P()),
                                             psh, osh),
                              donate_argnums=(0, 1)).lower(
                                  pspecs, ospecs, bspecs)
        elif shape.kind == "prefill":
            bspecs = steps.input_specs(cfg, shape)
            bsh = shard_rules.batch_sharding(mesh, bspecs)
            lowered = jax.jit(steps.make_prefill_step(cfg),
                              in_shardings=(psh, bsh)).lower(pspecs,
                                                             bspecs)
        else:
            cspecs, ispec = steps.decode_extras(cfg, shape)
            csh = shard_rules.cache_sharding(cfg, mesh, cspecs)
            bspecs = steps.input_specs(cfg, shape)
            bsh = shard_rules.batch_sharding(mesh, bspecs)
            lowered = jax.jit(steps.make_serve_step(cfg),
                              in_shardings=(psh, csh, bsh["tokens"],
                                            NamedSharding(mesh, P())),
                              donate_argnums=(1,)).lower(
                                  pspecs, cspecs, bspecs["tokens"], ispec)
        txt = lowered.compile().as_text()

    meta_re = re.compile(r'op_name="([^"]*)"')
    rows = []
    agg = defaultdict(float)
    for line in txt.splitlines():
        m = re.search(rf"= (.+?) ({re.escape(args.kind)})(-start)?\(",
                      line)
        if not m or "-done(" in line:
            continue
        rbytes = 0
        for dm in _SHAPE_RE.finditer(m.group(1)):
            n = 1
            for d in dm.group(2).split(","):
                if d:
                    n *= int(d)
            rbytes += n * _BYTES[dm.group(1)]
        mm = meta_re.search(line)
        name = mm.group(1) if mm else "?"
        rows.append((rbytes, name))
        agg[name.split("/")[-1][:60]] += rbytes

    rows.sort(reverse=True)
    print(f"top {args.top} {args.kind} ops by result bytes "
          f"(1 layer-group, per device):")
    for rbytes, name in rows[:args.top]:
        print(f"  {rbytes/1e6:10.1f} MB  {name[-110:]}")
    print(f"\n{args.kind} count={len(rows)} "
          f"total={sum(r for r, _ in rows)/1e9:.2f} GB per layer-group")


if __name__ == "__main__":
    main()
