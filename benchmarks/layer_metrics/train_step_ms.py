"""Device programs: device time of the jitted train step per step, from the
``XLA Modules`` events of ``jit_step`` in the trace (first chip)."""

from benchmarks.harness import readers


def read(ctx):
    runs = readers.module_runs(ctx, "jit_step")
    if not runs:
        return None
    n, seconds = runs
    return 1e3 * seconds / n
