"""Trainer: mean time a step waits for its batch in the ingest cell, by the
benchmark's own clock around ``next(batches)`` (``get_dataset_shard`` ->
``iter_jax_batches``: block fetch, re-batching and the device_put). The step
plane's ``data_wait`` stage times the same seam from inside the program; it
is not read here because a worker cannot yet hand it out per step."""

from benchmarks.harness import arith


def read(ctx):
    waits = ctx.get("waits")
    if not waits:
        return None
    return 1e3 * arith.mean(waits)
