"""Trainer: the step plane's ``host_to_device`` stage per window step (the
device_put in ``iter_jax_batches``); moves ``train_tokens_per_s``."""

from benchmarks.harness import loops


def read(ctx):
    return loops.stage_ms(ctx, "host_to_device_ms")
