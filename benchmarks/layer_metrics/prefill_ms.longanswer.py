"""Engine: mean time an iteration's prefills (buckets 256-1024) hold the
loop, and so all 32 running streams, in the long-answer cell; moves
``serve_tokens_per_s``."""

from benchmarks.harness import loops


def read(ctx):
    return loops.prefill_ms(ctx)
