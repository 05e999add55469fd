#!/usr/bin/env python3
"""Check a kept profiler trace against the loop records of the same run:

    python tools/loop_trace_check.py --trace <file.xplane.pb | directory> --loops <session_dir>/loops

The engine and the trainer stamp their phases in ``time.time_ns()`` and wrap
them in ``jax.profiler.TraceAnnotation`` (``llm.*`` / ``train.*``). This tool
shows that the two are on one clock: each annotation of the trace's
``/host:CPU`` plane that carries a step number is held against the loop record
of that step, and the largest distance is printed (exit code 2 past 1 ms).
The stream records' stamps (``llm_stream``, ``serve_stream``) lie on the same
clock: ``streams_in_trace`` counts the engine's streams whose first token was
taken inside the traced window. Reads with JAX alone.

The ``jax.named_scope`` names of the device programs are not in such a trace
(PERF.md, Open questions): its device events are named by their HLO line
without metadata. They are in the compiled program's own HLO text, keyed by
the instruction names the events carry.
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import sys


def newest_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True), key=os.path.getmtime)
    if not files:
        raise SystemExit(f"no .xplane.pb under {path}")
    return files[-1]


def load_records(loops_dir: str) -> dict:
    out = collections.defaultdict(list)
    for path in sorted(glob.glob(os.path.join(loops_dir, "*.jsonl*"))):
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                out[rec.get("kind")].append(rec)
    return out


def profile_start_ns(pd) -> int:
    """``ProfileData`` gives an event's start in nanoseconds since the
    profiler session began; the session's own start, in ``time.time_ns()``,
    is the ``profile_start_time`` stat of the ``Task Environment`` plane."""
    for plane in pd.planes:
        for key, value in plane.stats:
            if key == "profile_start_time":
                return int(value)
    raise SystemExit("the trace does not say when it began (no profile_start_time)")


def trace_window_ns(pd) -> tuple:
    """The traced window in ``time.time_ns()``: the session's start, and the end of its last event."""
    t_base = profile_start_ns(pd)
    last = max((int(e.start_ns + e.duration_ns) for plane in pd.planes for line in plane.lines for e in line.events),
               default=0)
    return t_base, t_base + last


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--trace", required=True)
    ap.add_argument("--loops", required=True)
    ap.add_argument("--out")
    args = ap.parse_args()

    from jax.profiler import ProfileData

    path = newest_xplane(args.trace)
    records = load_records(args.loops)
    steps = {r["step"]: r for r in records.get("llm_step", ()) if r.get("t_dispatch")}
    train = {r["step"]: r for r in records.get("train_step", ())}
    pd = ProfileData.from_file(path)
    t_base, t_end = trace_window_ns(pd)

    offsets = collections.defaultdict(list)  # annotation -> |annotation edge - record stamp|, ns
    counts = collections.Counter()
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if not e.name.startswith(("llm.", "train.")):
                        continue
                    base, kw = e.name.partition("#")[0], dict(e.stats)  # the keyword arguments are stats
                    counts[base] += 1
                    start = t_base + int(e.start_ns)
                    end = start + int(e.duration_ns)
                    step = int(kw["step"]) if "step" in kw else None
                    if base == "llm.dispatch" and step in steps:
                        offsets[base].append(max(abs(start - steps[step]["t_dispatch"]),
                                                 abs(end - steps[step]["t_dispatch_end"])))
                    elif base == "llm.retire" and step is not None:
                        # step k is retired in the iteration that dispatches k+2 (the loop
                        # one step ahead), k+1 (one step in flight) or none
                        recs = [steps[s] for s in (step + 2, step + 1, step) if s in steps and steps[s].get("t_retire_end")]
                        if recs:
                            offsets[base].append(min(abs(end - r["t_retire_end"]) for r in recs))
                    elif base == "train.report" and step in train and train[step].get("t2_ns"):
                        offsets[base].append(abs(end - train[step]["t2_ns"]))
    result = {
        "trace": path,
        "profile_start_ns": t_base,
        "annotations": dict(counts),
        "streams_in_trace": sum(1 for r in records.get("llm_stream", ()) if t_base <= r["t_first_taken"] <= t_end),
        "matched": {k: len(v) for k, v in offsets.items()},
        "max_offset_ms": {k: max(v) / 1e6 for k, v in offsets.items()},
        "mean_offset_ms": {k: sum(v) / len(v) / 1e6 for k, v in offsets.items()},
    }
    print(json.dumps(result, indent=1))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    worst = max((max(v) for v in offsets.values()), default=None)
    if worst is None:
        print("no annotation could be held against a loop record", file=sys.stderr)
        return 1
    return 0 if worst < 1_000_000 else 2


if __name__ == "__main__":
    sys.exit(main())
