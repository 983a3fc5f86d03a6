"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Absolute numbers are for this CPU
container; ``derived`` columns carry the per-figure derived quantity
(GFlop/s, byte models, correlations, v5e-model projections).  Run:

  PYTHONPATH=src python -m benchmarks.run [--only fig4,table2]
"""
import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated figure keys")
    args = ap.parse_args()
    from repro.core.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import (
        fig1_read_bw,
        fig2_write_bw,
        fig4_spmv,
        fig5_ucld,
        fig6_bandwidth,
        fig7_scaling,
        fig8_rcm,
        fig9_spmm,
        fig10_arch_comparison,
        fig11_autotune,
        fig12_engine,
        fig13_mesh_engine,
        fig14_imbalance,
        fig15_dispatch,
        fig16_spmspv,
        fig17_solver,
        fig18_fleet,
        fig19_chaos,
        fig20_overload,
        table2_register_blocking,
    )

    figures = {
        "fig1": fig1_read_bw,
        "fig2": fig2_write_bw,
        "fig4": fig4_spmv,
        "fig5": fig5_ucld,   # consumes fig4 results; keep ordered after it
        "fig6": fig6_bandwidth,
        "fig7": fig7_scaling,
        "fig8": fig8_rcm,
        "table2": table2_register_blocking,
        "fig9": fig9_spmm,
        "fig10": fig10_arch_comparison,
        "fig11": fig11_autotune,
        "fig12": fig12_engine,
        "fig13": fig13_mesh_engine,  # shard sweep adapts to visible devices
        "fig14": fig14_imbalance,
        "fig15": fig15_dispatch,
        "fig16": fig16_spmspv,
        "fig17": fig17_solver,
        "fig18": fig18_fleet,
        "fig19": fig19_chaos,
        "fig20": fig20_overload,
    }
    only = set(args.only.split(",")) if args.only else None
    lines: list = ["name,us_per_call,derived"]
    errors = []
    for key, mod in figures.items():
        if only and key not in only:
            continue
        t0 = time.time()
        try:
            mod.main(lines)
            status = f"ok in {time.time()-t0:.0f}s"
        except Exception as e:
            lines.append(f"{key}_ERROR,0.0,{type(e).__name__}:{e}")
            status = f"ERROR {e}"
            errors.append(key)
        print(f"# [{key}] {status}", file=sys.stderr, flush=True)
    print("\n".join(lines), flush=True)
    if errors:  # every figure still ran, but the run did not succeed
        sys.exit(f"figures failed: {', '.join(errors)}")


if __name__ == "__main__":
    main()
