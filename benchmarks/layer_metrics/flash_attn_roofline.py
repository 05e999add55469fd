"""Kernels (``ops/attention.py``): the least time the chip could take for one
step's attention, forward and backward over every layer (causal FLOPs over
the bf16 peak, q/k/v/o bytes over the memory bandwidth, the larger), over the
device time of the Pallas kernels in the traced steps: the ``tpu_custom_call``
events named ``*flash_attention*`` (forward, and forward again where the
backward pass recomputes it) and ``flash_mha_bwd_*``. Work is counted once,
time as spent: recomputation lowers the share. Per chip: a mesh splits the
batch over data x fsdp and the heads over tensor."""

from benchmarks.harness import readers, rooflines

KERNELS = ("flash_attention", "flash_mha")


def read(ctx):
    trace, peaks = ctx.get("trace"), ctx.get("peaks")
    runs = readers.module_runs(ctx, "jit_step")
    if not trace or not peaks or not runs:
        return None
    seconds = sum(s for name, s in trace["ops_s"].items()
                  if name.startswith("jit_step") and any(k in name for k in KERNELS))
    if not seconds:
        return None
    model, tc = ctx["model"], ctx["train"]
    mesh = tc["mesh"]
    batch_split = max(1, mesh.get("fsdp", 1)) * (ctx["chips"] if mesh.get("data") == -1 else max(1, mesh.get("data", 1)))
    head_split = max(1, mesh.get("tensor", 1))
    call = rooflines.flash_attention_call(
        tc["batch"] // batch_split, model["n_heads"] // head_split, tc["seq"],
        model["d_model"] // model["n_heads"])
    least = sum(rooflines.least_time_s(call[p]["flops"], call[p]["bytes"], peaks)["seconds"]
                for p in ("fwd", "bwd")) * model["n_layers"]
    return 100.0 * least / (seconds / runs[0])
