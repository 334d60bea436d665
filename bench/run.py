#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``--control fp8`` runs the cell as its correctness control instead: one
precision below the configuration's bfloat16, the rollout engine serving
float8 (e4m3) weights through the program's own ``rollout_quant="fp8"``
path and, where the cell trains, the reference's train step with float8
matmul operands (and, as the planted fault "half of the batch left out",
fed half of each batch) in the program's place.  Its readings set each
limit's upper end; its ``correct`` must come out false.  The benchmark's
own runs never pass it.

Prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
``breakdown`` (traced runs) and, last, ``checks``: each number the
correctness comparison compared, beside its limit.  Exits 2, printing no
result, where JAX finds no TPU or fewer chips than the cell asks for, or a
``device_kind`` the peaks table does not know.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from benchlib import harness  # noqa: E402
from benchlib.peaks import peaks_for  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("fp8",), default=None)
    args = ap.parse_args(argv)

    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell = harness.find_cell(bench, args.workload)
    harness.enable_cache()
    try:
        devices = harness.tpu_devices(int(cell["chips"]))
        peaks = peaks_for(devices[0].device_kind)
    except (harness.NoDevice, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    scratch = harness.scratch_dir() if args.trace else None
    try:
        ctx = harness.cell_context(
            args.workload, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), t_start=T_START, devices=devices,
            peaks=peaks, control=args.control, scratch=scratch)
        outcome = harness.run_driver(ctx)
        harness.print_result(harness.result_line(ctx, outcome))
    finally:
        if scratch is not None:
            shutil.rmtree(scratch, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
