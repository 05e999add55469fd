"""Kernels (``ops/selective_scan.py:selective_scan_update``): the least time the
chip could take for one decode step's state-space updates, a call a state-space
layer (each live sequence's (N, d_in) float32 state read once and written once,
with its ``c``, ``Dl``, ``B`` and ``C`` read and its ``y`` written, over the
memory bandwidth; its FLOPs over the peak; the larger; counted by the
configuration's family, ``ssm_update_need``), over the device time of the
``selective_scan_update`` events of ``jit_decode_step_greedy`` in the traced
steps. The sequences a step come from the engine's loop records over the window
(``live``), the mean over the steps that dispatched. A state's bytes do not grow
with the context, so nothing else is read. A program without the kernel (any
program off the chip; a family without state-space layers) has no such event,
or no such count, and the line leaves the metric out."""

from benchmarks import families
from benchmarks.harness import arith, loops, readers, rooflines

PROGRAM, KERNEL = "jit_decode_step_greedy", "selective_scan_update"


def read(ctx):
    trace, peaks = ctx.get("trace"), ctx.get("peaks")
    runs = readers.module_runs(ctx, PROGRAM)
    need_of = getattr(families.of(ctx["config"]), "ssm_update_need", None)
    if not trace or not peaks or not runs or need_of is None:
        return None
    seconds = sum(s for name, s in trace["ops_s"].items() if name.startswith(PROGRAM) and KERNEL in name)
    steps = [r for r in loops.engine_steps(ctx) if r["live"]]
    if not seconds or not steps:
        return None
    need = need_of(ctx["model"], arith.mean([r["live"] for r in steps]))
    least = rooflines.least_time_s(need["flops"], need["bytes"], peaks)
    return 100.0 * least["seconds"] / (seconds / runs[0])
