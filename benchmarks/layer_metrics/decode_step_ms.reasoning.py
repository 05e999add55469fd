"""Engine: mean decode step in the reasoning cell (48 slots), retire to
retire; moves ``serve_tokens_per_s``."""

from benchmarks.harness import readers


def read(ctx):
    return readers.decode_step_ms(ctx)
