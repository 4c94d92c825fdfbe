"""Roofline analysis from dry-run artifacts (EXPERIMENTS.md §Roofline).

Per (arch x shape x mesh) record (produced by repro.launch.dryrun):

  compute_s    = HLO_FLOPs / peak_FLOPs            (per device)
  memory_s     = HLO_bytes / HBM_bw                (per device)
  collective_s = ring wire bytes / (links x link_bw) (per device)

Hardware constants: the v5e entry of ``repro.core.cost.CHIPS`` (197
TFLOP/s bf16, 819 GB/s HBM), ~50 GB/s/link ICI, 4 links/chip usable on
a 2-D torus axis pair.
HLO FLOPs/bytes are the scan-extrapolated per-device totals (XLA counts
a while body once; the dry-run recovers multiplicity by compiling 1- and
2-group unrolled variants -- see dryrun.py).
"""
from __future__ import annotations

import argparse
import json
from typing import Any, Dict

from repro.core.cost import CHIPS, DSE_TARGET

PEAK_FLOPS = CHIPS[DSE_TARGET].peak_flops
HBM_BW = CHIPS[DSE_TARGET].hbm_bytes_per_s
LINK_BW = 50e9
N_LINKS = 4


def analyze_record(r: Dict[str, Any]) -> Dict[str, Any]:
    flops = r["cost_per_device_scanned"]["flops"]
    hbm = r["cost_per_device_scanned"]["bytes_accessed"]
    wire = r["collective_wire_bytes_scanned"]["total"]
    n = r["n_devices"]
    compute_s = flops / PEAK_FLOPS
    memory_s = hbm / HBM_BW
    collective_s = wire / (N_LINKS * LINK_BW)
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    # useful fraction: analytic model cost vs what the machine must do
    # at the bound.  Train/prefill are compute-characterized (6ND/2ND);
    # decode is memory-characterized: the analytic floor is one read of
    # (active params + caches + step inputs) per step.
    if r["shape"].startswith(("decode", "long")):
        arg_bytes = r["memory_per_device"]["argument_bytes"]
        model_s = arg_bytes / HBM_BW  # must at least stream the state
    else:
        model_s = r["model_flops"] / n / PEAK_FLOPS
    frac = model_s / bound if bound > 0 else 0.0
    return {
        "arch": r["arch"], "shape": r["shape"], "mesh": r["mesh"],
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": collective_s, "dominant": dominant,
        "model_flops": r["model_flops"],
        "hlo_flops_total": flops * n,
        "useful_ratio": r["model_flops"] / (flops * n) if flops else 0.0,
        "roofline_fraction": frac,
        "step_s_bound": bound,
        "memory_per_device_gb":
            (r["memory_per_device"]["argument_bytes"]
             + r["memory_per_device"]["temp_bytes"]) / 1e9,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("results", nargs="+",
                    help="JSONL files from repro.launch.dryrun")
    ap.add_argument("--markdown", action="store_true")
    args = ap.parse_args()
    rows = []
    for path in args.results:
        with open(path) as f:
            for line in f:
                r = json.loads(line)
                if "skipped" in r:
                    rows.append({"arch": r["arch"], "shape": r["shape"],
                                 "mesh": r["mesh"],
                                 "skipped": r["skipped"]})
                elif "error" in r:
                    rows.append({"arch": r["arch"], "shape": r["shape"],
                                 "mesh": r["mesh"], "error": r["error"]})
                else:
                    rows.append(analyze_record(r))
    if args.markdown:
        hdr = ("| arch | shape | mesh | compute_s | memory_s | coll_s | "
               "bound | frac | useful | mem GB |")
        print(hdr)
        print("|" + "---|" * 10)
        for a in rows:
            if "skipped" in a:
                print(f"| {a['arch']} | {a['shape']} | {a['mesh']} | "
                      f"SKIP ({a['skipped'][:40]}...) |||||||")
                continue
            if "error" in a:
                print(f"| {a['arch']} | {a['shape']} | {a['mesh']} | "
                      f"ERROR |||||||")
                continue
            print(f"| {a['arch']} | {a['shape']} | {a['mesh']} "
                  f"| {a['compute_s']:.4f} | {a['memory_s']:.4f} "
                  f"| {a['collective_s']:.4f} | {a['dominant']} "
                  f"| {a['roofline_fraction']:.3f} "
                  f"| {a['useful_ratio']:.2f} "
                  f"| {a['memory_per_device_gb']:.1f} |")
    else:
        for a in rows:
            print(json.dumps(a))


if __name__ == "__main__":
    main()
