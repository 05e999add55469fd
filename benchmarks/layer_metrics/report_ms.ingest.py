"""Trainer: time ``train.report`` blocks the loop per window step (the step
plane's ``report`` stage: the collector round trip); moves
``train_tokens_per_s``."""

from benchmarks.harness import loops


def read(ctx):
    return loops.stage_ms(ctx, "report_ms")
