"""Trainer: the step plane's ``data_wait`` stage per window step, timed
inside ``iter_batches`` (``data_wait_ms.ingest`` times the same seam and the
device_put from outside); moves ``train_tokens_per_s``."""

from benchmarks.harness import loops


def read(ctx):
    return loops.stage_ms(ctx, "data_wait_ms")
