"""Engine: mean decode step in the long-answer cell (32 slots); moves
``serve_tokens_per_s``."""

from benchmarks.harness import readers


def read(ctx):
    return readers.decode_step_ms(ctx)
