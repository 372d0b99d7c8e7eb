"""Benchmark runner — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--scale 1.0] [--only fig10,...]
                                            [--json [PATH]]

Prints ``bench,name,value,unit,notes`` CSV to stdout; ``--json`` also
writes the rows (plus run metadata) as JSON — the artifact the nightly
workflow uploads and feeds to ``benchmarks/check_regression.py``.
``--json`` with no PATH writes the stable default ``BENCH_latest.json``
at the repo root, which is also ``check_regression.py``'s default
``--result`` — so ``run.py --json`` followed by ``check_regression.py``
just works.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import platform
import sys
import time
import traceback

# stable, repo-root-anchored artifact name: the latest sweep lands in
# the same place no matter the working directory the runner used
DEFAULT_JSON = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_latest.json",
)

MODULES = (
    "fig10_long_reads",
    "fig11_pair_selection",
    "fig12_short_reads",
    "fig13_deferred_write",
    "fig14_format_flex",
    "fig15_write_throughput",
    "fig16_eviction",
    "fig17_joint_storage",
    "fig18_joint_throughput",
    "fig19_joint_overhead",
    "fig20_zstd_read",
    "fig21_end_to_end",
    "fig22_backend_scaling",
    "fig23_batch_reads",
    "fig24_ingest_pipeline",
    "fig25_replication",
    "fig26_remote",
    "fig27_serving",
    "fig28_subgop",
    "fig29_adaptive",
    "table2_joint_quality",
    "roofline",
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--only", default=None,
                    help="comma-separated module prefixes")
    ap.add_argument("--json", nargs="?", const=DEFAULT_JSON, default=None,
                    metavar="PATH",
                    help="also write rows + metadata as JSON (default"
                         " PATH: BENCH_latest.json at the repo root)")
    args = ap.parse_args()
    only = args.only.split(",") if args.only else None

    print("bench,name,value,unit,notes")
    failed = []
    collected = []
    for mod_name in MODULES:
        if only and not any(mod_name.startswith(o) for o in only):
            continue
        t0 = time.perf_counter()
        try:
            mod = importlib.import_module(f"benchmarks.{mod_name}")
            for row in mod.run(args.scale):
                print(row.csv(), flush=True)
                collected.append(row)
            print(f"# {mod_name} done in {time.perf_counter()-t0:.1f}s",
                  flush=True)
        except Exception as e:
            failed.append(mod_name)
            print(f"# {mod_name} FAILED: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc(file=sys.stderr)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({
                "scale": args.scale,
                "platform": platform.platform(),
                "python": platform.python_version(),
                "failed_modules": failed,
                "rows": [
                    {"bench": r.bench, "name": r.name, "value": r.value,
                     "unit": r.unit, "notes": r.notes}
                    for r in collected
                ],
            }, f, indent=2)
    if failed:
        raise SystemExit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    from repro.utils import init_compile_cache

    init_compile_cache()
    main()
