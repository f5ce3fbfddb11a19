"""Benchmark harness — one module per paper table/figure.

  Fig 8a  -> microbench   (gather/scatter/RMW, engine vs naive)
  Fig 8bc -> locality     (index locality sweep: traffic + coalescing)
  Fig 9/10-> workloads    (embedding grad, MoE dispatch, paged KV, train,
                           Table-1 conformance patterns)
  Fig 13  -> tilesize     (bulk tile-size sensitivity)

Output: ``name,us_per_call,derived`` CSV on stdout. With ``--json``, each
module additionally writes ``BENCH_<name>.json`` (a machine-readable
snapshot for tracking the perf trajectory across PRs).
Roofline-derived TPU numbers live in EXPERIMENTS.md (from the dry-run).
"""
from __future__ import annotations

import argparse
import json
import platform
from pathlib import Path


def main() -> None:
    from benchmarks import (common, kv_bench, locality, microbench,
                            pipeline_bench, scheduler_bench, sharded_bench,
                            tilesize, traffic_bench, workloads)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("only", nargs="?", default=None,
                    choices=("microbench", "locality", "workloads",
                             "tilesize", "scheduler", "sharded",
                             "pipeline", "traffic", "kv"),
                    help="run a single module (default: all)")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_<module>.json in the cwd")
    args = ap.parse_args()

    print("name,us_per_call,derived")
    for name, mod in (("microbench", microbench), ("locality", locality),
                      ("workloads", workloads), ("tilesize", tilesize),
                      ("scheduler", scheduler_bench),
                      ("sharded", sharded_bench),
                      ("pipeline", pipeline_bench),
                      ("traffic", traffic_bench),
                      ("kv", kv_bench)):
        if args.only and args.only != name:
            continue
        print(f"# --- {name} ---", flush=True)
        common.RESULTS.clear()
        mod.run()
        if args.json:
            payload = {"bench": name,
                       "platform": platform.platform(),
                       "results": list(common.RESULTS)}
            path = Path(f"BENCH_{name}.json")
            path.write_text(json.dumps(payload, indent=2) + "\n")
            print(f"# wrote {path}", flush=True)


if __name__ == "__main__":
    main()
