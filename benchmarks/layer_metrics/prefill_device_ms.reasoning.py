"""Device programs: device time of ``jit_prefill`` a call in the reasoning
cell, from the ``XLA Modules`` events of the traced window (the mean over the
buckets that ran there). Since PR 30 ``prefill_ms.*`` is the host's time to
enqueue a prefill; this is what one costs the device, which the decode steps
queue behind. Moves ``serve_tokens_per_s``."""

from benchmarks.harness import readers


def read(ctx):
    runs = readers.module_runs(ctx, "jit_prefill")
    if not runs:
        return None
    n, seconds = runs
    return 1e3 * seconds / n
