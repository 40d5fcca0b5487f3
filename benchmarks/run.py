"""Benchmark harness — one module per paper table/claim + framework benches.

Prints ``name,us_per_call,derived...`` CSV rows.  Usage:
  PYTHONPATH=src python -m benchmarks.run [--only storage,licensing,...]
  PYTHONPATH=src python -m benchmarks.run --smoke       # CI smoke lane
  PYTHONPATH=src python -m benchmarks.run --json out/   # machine-readable

``--smoke`` runs every suite at reduced scale (suites whose ``run``
accepts a ``smoke`` kwarg shrink their workloads) so CI can assert the
perf scripts still execute end to end without burning minutes.

``--json DIR`` additionally writes one ``BENCH_<suite>.json`` per suite
(full row dicts plus run metadata) so the perf trajectory is tracked as
an artifact across PRs instead of scraped from CI logs.
"""
from __future__ import annotations

import argparse
import inspect
import json
import pathlib
import sys
import time
import traceback

SUITES = ("storage", "update-wire", "licensing", "kernels", "serving",
          "gateway", "paging", "prefix", "decode", "update", "prefill",
          "fleet", "telemetry", "chaos", "roofline")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help=f"comma list from {SUITES}")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced-scale run for CI (suites may shrink "
                         "workloads; all assertions still fire)")
    ap.add_argument("--json", default=None, metavar="DIR",
                    help="also write BENCH_<suite>.json result files "
                         "into DIR (created if missing)")
    args = ap.parse_args(argv)
    picked = args.only.split(",") if args.only else list(SUITES)
    json_dir = None
    if args.json is not None:
        json_dir = pathlib.Path(args.json)
        json_dir.mkdir(parents=True, exist_ok=True)

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()   # every suite runs in this process: one cache

    from benchmarks import (chaos_bench, decode_bench, fleet_bench,
                            gateway_bench, kernel_bench, licensing_ladder,
                            paging_bench, prefill_bench, prefix_bench,
                            roofline_table, serving_bench, storage_cost,
                            telemetry_bench, update_bench, update_latency)

    modules = {
        "storage": storage_cost,        # paper Table 1
        "update-wire": update_latency,  # paper §4.3 bytes-on-the-wire
        "licensing": licensing_ladder,  # paper §3.5 / Algorithm 1
        "kernels": kernel_bench,
        "serving": serving_bench,
        "gateway": gateway_bench,       # continuous batching vs single-stream
        "paging": paging_bench,         # block-paged vs fixed-lane cache pool
        "prefix": prefix_bench,         # shared-prefix radix cache vs paged
        "decode": decode_bench,         # kernel-resident vs gather/scatter
        "update": update_bench,         # staged sync vs blocking decode stall
        "prefill": prefill_bench,       # chunked prefill decode-stall SLO
        "fleet": fleet_bench,           # multi-model fleet vs isolated
        "telemetry": telemetry_bench,   # observability <3% overhead gate
        "chaos": chaos_bench,           # fault-schedule stall + equivalence
        "roofline": roofline_table,     # deliverable (g)
    }

    failures = 0
    print("name,us_per_call,derived")
    for name in picked:
        mod = modules[name]
        kw = {}
        if args.smoke and "smoke" in inspect.signature(mod.run).parameters:
            kw["smoke"] = True
        try:
            rows = list(mod.run(**kw))
            for row in rows:
                derived = {k: v for k, v in row.items()
                           if k not in ("name", "us_per_call")}
                print(f"{row['name']},{row['us_per_call']:.1f},"
                      + json.dumps(derived, default=str))
            if json_dir is not None:
                out = {"suite": name, "smoke": bool(args.smoke),
                       "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
                       "rows": rows}
                (json_dir / f"BENCH_{name}.json").write_text(
                    json.dumps(out, indent=2, default=str) + "\n")
        except Exception:  # noqa: BLE001 — report all suites
            failures += 1
            print(f"{name},FAILED,", file=sys.stdout)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
