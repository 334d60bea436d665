"""Benchmark harness entry: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only fig1b,fig7,...]

Emits ``name,value,derived`` CSV rows (captured to bench_output.txt by the
final deliverable run).  BENCH_FULL=1 enables the long fig4 training runs.
"""
from __future__ import annotations

import argparse
import time

from benchmarks import (fig1b_throughput_scaling,
                        fig3_allocation_and_rollout, fig4_offpolicy_stability,
                        fig7_queue_scheduling, fig8_prompt_replication,
                        fig9_env_async, fig10_redundant_env,
                        fig11_real_agentic, roofline, table1_async_ratio)
from benchmarks.common import emit, flush_csv

MODULES = [
    ("fig1b", fig1b_throughput_scaling),
    ("fig3", fig3_allocation_and_rollout),
    ("table1", table1_async_ratio),
    ("fig7", fig7_queue_scheduling),
    ("fig8", fig8_prompt_replication),
    ("fig9", fig9_env_async),
    ("fig10", fig10_redundant_env),
    ("fig4", fig4_offpolicy_stability),
    ("fig11", fig11_real_agentic),
    ("roofline", roofline),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module prefixes")
    ap.add_argument("--csv", default=None)
    args = ap.parse_args()
    selected = args.only.split(",") if args.only else None

    for name, mod in MODULES:
        if selected and name not in selected:
            continue
        t0 = time.time()
        print(f"# --- {name} ---")
        mod.run()
        emit(f"_time.{name}_s", time.time() - t0, "")
    flush_csv(args.csv)


if __name__ == "__main__":
    main()
