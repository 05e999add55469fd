"""Device programs: held experts that got at least one row, an expert layer a
decode step, over the window of the reasoning cell: what a step reads of its
routed experts' weights (the family's ``decode_step_need`` counts on
``experts_touched(model, batch)`` of them). From the engine's ``llm_moe`` loop
records (``harness/routing.py``). None on a program that writes no such
records; moves ``serve_tokens_per_s``."""

from benchmarks.harness import routing


def read(ctx):
    counts = routing.window_counts(ctx, "touched")
    if counts is None:
        return None
    (touched,), layer_steps = counts
    return touched / layer_steps
