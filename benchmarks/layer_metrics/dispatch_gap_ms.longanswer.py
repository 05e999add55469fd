"""Engine: mean host time from a step's result on the host to the return of
the next step's dispatch (``t_dispatch_end - t_result``) in the long-answer
cell: retire, detach and dispatch at 32 slots, the work the device waits for;
moves ``serve_tokens_per_s``."""

from benchmarks.harness import loops


def read(ctx):
    return loops.dispatch_gap_ms(ctx)
