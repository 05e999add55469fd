"""Kernels (``ops/window_attention.py:ring_window_attention``): the least time
the chip could take for one decode step's window attention, a call a window
layer (the live rows of the dispatched sequences' rings, K and V of every
published head, with each sequence's queries read and its output written, over
the memory bandwidth; its FLOPs over the peak; the larger; counted by the
configuration's family, ``window_attention_need``), over the device time of the
``ring_window_attention`` events of ``jit_decode_step_greedy`` in the traced
steps. The rows a step come from the engine's loop records over the window
(``ring_rows``: the sum over the dispatched sequences of min(length, window),
and ``live``: how many were dispatched), the mean over the steps that
dispatched. A program without the kernel or without the count (any program off
the chip; a kind without rings; a program older than the count) has nothing to
read, and the line leaves the metric out."""

from benchmarks import families
from benchmarks.harness import arith, loops, readers, rooflines

PROGRAM, KERNEL = "jit_decode_step_greedy", "ring_window_attention"


def read(ctx):
    trace, peaks = ctx.get("trace"), ctx.get("peaks")
    runs = readers.module_runs(ctx, PROGRAM)
    need_of = getattr(families.of(ctx["config"]), "window_attention_need", None)
    if not trace or not peaks or not runs or need_of is None:
        return None
    seconds = sum(s for name, s in trace["ops_s"].items() if name.startswith(PROGRAM) and KERNEL in name)
    steps = [r for r in loops.engine_steps(ctx) if r["live"] and r.get("ring_rows")]
    if not seconds or not steps:
        return None
    need = need_of(ctx["model"], arith.mean([r["ring_rows"] for r in steps]), arith.mean([r["live"] for r in steps]))
    least = rooflines.least_time_s(need["flops"], need["bytes"], peaks)
    return 100.0 * least["seconds"] / (seconds / runs[0])
