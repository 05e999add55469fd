"""Kernels: the least time one decode step could take on this chip (the
weights once plus the live KV rows read, over the memory bandwidth; its FLOPs
over the peak; the larger; counted by the configuration's family) over the
decode program's device time in the trace. This is the whole
``jit_decode_step_greedy`` program: scatter, the paged-attention kernel, MLP,
head and argmax; the kernel's own share is ``paged_attn_roofline``. Live rows
per step come from the engine's counters over the window: the mean number of
sequences in a step times the mean context they hold."""

from benchmarks import families
from benchmarks.harness import readers, rooflines


def read(ctx):
    runs = readers.module_runs(ctx, "jit_decode_step_greedy")
    c = ctx.get("counters") or {}
    if not runs or not c.get("decode_steps") or not ctx.get("peaks"):
        return None
    n, seconds = runs
    batch = (c["decode_tokens"] - c["first_tokens"]) / c["decode_steps"]
    live_rows = batch * ctx["mean_context"]
    need = families.of(ctx["config"]).decode_step_need(ctx["model"], batch, live_rows)
    least = rooflines.least_time_s(need["flops"], need["bytes"], ctx["peaks"])
    return 100.0 * least["seconds"] / (seconds / n)
