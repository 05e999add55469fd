"""Engine: mean host time from a step's result on the host to the return of
the next step's dispatch (``t_dispatch_end - t_result``): retire, detach and
dispatch, the work the device waits for; moves ``serve_tokens_per_s``."""

from benchmarks.harness import loops


def read(ctx):
    return loops.dispatch_gap_ms(ctx)
