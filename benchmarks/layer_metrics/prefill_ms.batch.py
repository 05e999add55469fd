"""Engine: mean time an iteration's prefills hold the loop, and so every
running stream (``t_admit_end - t_loop`` of the loop records with a
prefill); moves ``serve_tokens_per_s``."""

from benchmarks.harness import loops


def read(ctx):
    return loops.prefill_ms(ctx)
