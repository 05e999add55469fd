"""Device programs: how uneven the router's choice leaves the held experts'
load, over the window of the reasoning cell: the rows of the held expert that
got the most, a layer a decode step, over the rows a touched expert got in the
mean (``peak`` over ``held / touched`` of the engine's ``llm_moe`` loop
records, ``harness/routing.py``; 1 = even, and the grouped matmul's time
follows the largest group). None on a program whose records carry no
``peak``; moves ``serve_tokens_per_s``."""

from benchmarks.harness import routing


def read(ctx):
    counts = routing.window_counts(ctx, "held", "touched", "peak")
    if counts is None:
        return None
    (held, touched, peak), layer_steps = counts
    if held <= 0 or touched <= 0:
        return None
    return (peak / layer_steps) / (held / touched)
