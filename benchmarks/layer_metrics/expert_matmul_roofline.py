"""Kernels (``models/moe.py:grouped_matmul``): the least time the chip could
take for one decode step's grouped matmuls, three an expert layer (the touched
held experts' three matrices once, the routed rows in, the hidden rows between
and the result rows out, over the memory bandwidth; the rows' FLOPs over the
peak; the larger; counted by the configuration's family,
``expert_matmul_need``), over the device time of the grouped-matmul kernel's
events (``gmm``) of ``jit_decode_step_greedy`` in the traced steps. The touched
experts and the rows an expert layer a step come from the engine's ``llm_moe``
loop records over the window (``touched`` and ``held`` over decode steps x
expert layers: ``harness/routing.py``). It reads the work, not the
implementation: the windows a call walks and the rows a window pads are the
kernel's to pay. A program without the kernel or without the counts (any program
off the chip; a kind without experts; a family that does not count the kernel)
has nothing to read, and the line leaves the metric out."""

from benchmarks import families
from benchmarks.harness import readers, rooflines, routing

PROGRAM, KERNEL = "jit_decode_step_greedy", "gmm"


def read(ctx):
    trace, peaks = ctx.get("trace"), ctx.get("peaks")
    runs = readers.module_runs(ctx, PROGRAM)
    need_of = getattr(families.of(ctx["config"]), "expert_matmul_need", None)
    if not trace or not peaks or not runs or need_of is None:
        return None
    seconds = sum(s for name, s in trace["ops_s"].items()
                  if name.startswith(PROGRAM) and name.rsplit("/", 1)[-1].split(".")[0] == KERNEL)
    counts = routing.window_counts(ctx, "held", "touched")
    if not seconds or counts is None:
        return None
    (held, touched), layer_steps = counts
    need = need_of(ctx["model"], touched / layer_steps, held / layer_steps)
    least = rooflines.least_time_s(need["flops"], need["bytes"], peaks)
    return 100.0 * least["seconds"] / (seconds / runs[0])
