"""Benchmark harness — one module per paper table/figure + beyond-paper
pod-scale benchmarks + the roofline table.  Prints name,us_per_call,derived
CSV (see common.row).

    PYTHONPATH=src python -m benchmarks.run [--quick]
"""

from __future__ import annotations

import argparse
import sys
import traceback

from repro.launch.compile_cache import enable_compile_cache

from . import common
from . import (chaos_serving, disagg_serving, fig5_heatmap, fig6_kernels,
               fig7_speedup, fig8_interference, fig9_vgg_scaling,
               fig10_widths, fleet_routing, kernel_bench, pod_serving,
               pod_straggler, region_routing, roofline, serve_decode)

MODULES = (
    ("chaos_serving", chaos_serving),
    ("disagg_serving", disagg_serving),
    ("fig5_heatmap", fig5_heatmap),
    ("fig6_kernels", fig6_kernels),
    ("fig7_speedup", fig7_speedup),
    ("fig8_interference", fig8_interference),
    ("fig9_vgg_scaling", fig9_vgg_scaling),
    ("fig10_widths", fig10_widths),
    ("fleet_routing", fleet_routing),
    ("kernel_bench", kernel_bench),
    ("pod_serving", pod_serving),
    ("pod_straggler", pod_straggler),
    ("region_routing", region_routing),
    ("roofline", roofline),
    ("serve_decode", serve_decode),
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    enable_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    for name, mod in MODULES:
        if args.only and args.only not in name:
            continue
        with common.measured_block() as m:
            try:
                mod.main(quick=args.quick)
            except Exception:
                traceback.print_exc()
                failed.append(name)
        print(f"# {name} done in {m.seconds:.1f}s", file=sys.stderr)
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
