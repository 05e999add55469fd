"""Kernels (``ops/paged_attention.py``): the least time the chip could take
for one decode step's paged attention, a call a layer (the whole blocks the
kernel copies, K and V, with each sequence's query read and output written,
over the memory bandwidth; its FLOPs over the peak; the larger; counted by the
configuration's family), over the device time of the ``paged_decode_attention``
events of ``jit_decode_step_greedy`` in the traced steps. The blocks a step
come from the engine's loop records over the window (``kv_blocks``: the live
blocks of the dispatched sequences' tables, and ``live``: how many were
dispatched), the mean over the steps that dispatched, as
``paged_decode_roofline`` takes ``mean_context``."""

from benchmarks import families
from benchmarks.harness import arith, loops, readers, rooflines

PROGRAM, KERNEL = "jit_decode_step_greedy", "paged_decode_attention"


def read(ctx):
    trace, peaks = ctx.get("trace"), ctx.get("peaks")
    runs = readers.module_runs(ctx, PROGRAM)
    if not trace or not peaks or not runs:
        return None
    seconds = sum(s for name, s in trace["ops_s"].items() if name.startswith(PROGRAM) and KERNEL in name)
    steps = [r for r in loops.engine_steps(ctx) if r["live"] and r.get("kv_blocks")]
    if not seconds or not steps:
        return None
    need = families.of(ctx["config"]).paged_attention_need(
        ctx["model"], arith.mean([r["kv_blocks"] for r in steps]), ctx["engine"]["block_size"],
        arith.mean([r["live"] for r in steps]))
    least = rooflines.least_time_s(need["flops"], need["bytes"], peaks)
    return 100.0 * least["seconds"] / (seconds / runs[0])
