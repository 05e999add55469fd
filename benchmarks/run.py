#!/usr/bin/env python3
"""The benchmark's one command:

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process tree a run. This process never touches JAX: the worker that holds
the ``TPU`` resource does all device work, and every process is gone when the
last line is printed. The last line of standard output is the result object;
everything else goes on earlier lines. A run that finds no TPU (or fewer chips
than the cell asks for, or a device that ``peaks.json`` does not list) prints
no result and exits non-zero.

The cell's files are found by name (``README.md``): its configuration
``configs/<config>.json``, its mix ``traffic/<traffic>.json`` whose ``kind``
chooses the driver, and one reader per per-layer metric
``layer_metrics/<name>.py``.

``--rehearse 1`` runs the same code where there is no chip (tests, at tiny
sizes); it exits with 3 and its line is never a chip result. ``--control 1``
also prints what the control of `correct` reads (see PERF.md). ``--keep-trace 1``
leaves a traced run's ``.xplane.pb`` under ``chiprun_out/trace/``.
"""

from __future__ import annotations

import argparse
import importlib
import os
import sys
import time

T_START = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

KINDS = {"closed_loop": "serve_cell", "open_loop": "serve_cell", "train_job": "train_cell"}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    args.t_start = T_START
    args.result = args.verdict = None  # the driver's: the result line's parts, the numbers compared beside their limits

    from benchmarks.harness import common

    common.export_environment()
    cell = common.load_cell(args.workload)
    kind = cell["traffic"]["kind"]
    if kind not in KINDS:
        raise SystemExit(f"traffic kind {kind!r} has no driver (known: {sorted(KINDS)})")
    driver = importlib.import_module("benchmarks.harness." + KINDS[kind])
    try:
        driver.run(cell, args)
    finally:
        killed = common.stop_processes()
    left = sorted(common._descendants(os.getpid()))
    if killed or left:
        raise SystemExit(f"benchmark: processes had to be killed {killed} or are left {left}")
    if "jax" in sys.modules:
        raise SystemExit("benchmark: the parent imported jax")
    if args.result is None:
        return 1
    print(args.verdict, file=sys.stderr, flush=True)  # the last line of standard error, whatever the teardown wrote
    common.emit(*args.result)
    return 3 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
