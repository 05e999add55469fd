"""Engine: mean decode step in the batch cell; moves ``serve_tokens_per_s``."""

from benchmarks.harness import readers


def read(ctx):
    return readers.decode_step_ms(ctx)
