#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result.

    python bench/run.py --workload ldoor.serve_open --seed 7 --seconds 40 --trace 0

The cell (``workloads`` in ``BENCHMARK.json``) names a configuration under
``bench/configs`` and a traffic mix under ``bench/traffic``, whose
``request`` names the cell's kind under ``bench/kinds``.  The run makes
the matrix's values and the requests from ``--seed``, loads or searches the
plans and warms every program up (``setup_s``), measures for ``--seconds``,
then checks what the measured window returned against a float64 reference.
``--trace 1`` profiles the window and reports the per-layer metrics
instead of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
also ``breakdown``), and last ``checks``, each compared number beside its
limit; the same numbers end standard error.  Without a TPU, or with fewer
chips than the cell asks for, the run prints no result and exits 3; without
the program's sources beside it, 2.

Caches live in the checkout: the plan cache and the generated pattern
under ``bench/.cache``, JAX's compilation cache where
``repro.core.compile_cache.enable_compile_cache`` puts it
(``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def log(msg: str) -> None:
    print(msg, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: no program sources under {src}; nothing was run",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    sys.path.insert(0, str(ROOT))
    from repro.core.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    from bench.lib.harness import NoAccelerator, run_cell

    try:
        result, _ = run_cell(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START, log=log)
    except NoAccelerator as exc:
        print(f"bench: {exc}; nothing was run", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
