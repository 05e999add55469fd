"""Engine: mean time the engine thread blocks for the in-flight step's
result (``t_result - t_admit_end``) in the long-answer cell; near 0 means the
host sets the pace; moves ``serve_tokens_per_s``."""

from benchmarks.harness import loops


def read(ctx):
    return loops.device_wait_ms(ctx)
