"""Device programs: the (token, choice) rows the held experts got, an expert
layer a decode step, over the window of the reasoning cell: the rows the
grouped matmuls multiply, which says on which side of the chip's ridge (about
240 rows of bfloat16) a decode step's window stands. ``held`` over the layer
steps of the engine's ``llm_moe`` loop records (``harness/routing.py``). None
on a program that writes no such records; moves ``serve_tokens_per_s``."""

from benchmarks.harness import routing


def read(ctx):
    counts = routing.window_counts(ctx, "held")
    if counts is None:
        return None
    (held,), layer_steps = counts
    return held / layer_steps
