"""Kernels (``ops/paged_attention.py:paged_latent_attention``): the least time
the chip could take for one decode step's attention over the latent cache, a
call an attention (the whole blocks the kernel copies, one stored row serving
as key and as value, with each sequence's queries read and output written,
over the memory bandwidth; its FLOPs, scores over a row's ``r_kv + d_r`` values
and the weighted sum over its ``r_kv``, for every head, over the peak; the
larger), over the device time of the ``paged_latent_attention`` events of
``jit_decode_step_greedy`` in the traced steps. The blocks a step come from the
engine's loop records over the window (``kv_blocks``, ``live``), the mean over
the steps that dispatched, as ``paged_attn_roofline`` takes them for GPT-J's
kernel. Whole copied blocks, not live rows: a share of this need cannot pass
100%. A program without the kernel (the parent of PR 35; any program off the
chip) has no such event and the line leaves the metric out."""

from benchmarks.harness import arith, loops, readers, rooflines

PROGRAM, KERNEL = "jit_decode_step_greedy", "paged_latent_attention"
LANES = 128


def attentions_a_step(model: dict) -> int:
    """From the published keys: Kimi-K2 (DeepSeek-V3's layer,
    ``num_hidden_layers``) has one latent attention a layer, LongCat-Flash
    (``num_layers``) two."""
    return model["num_hidden_layers"] if "num_hidden_layers" in model else 2 * model["num_layers"]


def latent_attention_need(model: dict, blocks: float, block_size: int, batch: float, itemsize: int = 2) -> dict:
    """The kernel's calls of one decode step: ``batch`` sequences whose tables
    hold ``blocks`` live blocks in all. A cache row is ``kv_lora_rank +
    qk_rope_head_dim`` values stored in whole lane tiles (576 in 640)."""
    heads, r_kv, d_r = model["num_attention_heads"], model["kv_lora_rank"], model["qk_rope_head_dim"]
    stored = -(-(r_kv + d_r) // LANES) * LANES
    rows, n = blocks * block_size, attentions_a_step(model)
    nbytes = (rows * stored + batch * heads * (r_kv + d_r) + batch * heads * r_kv) * itemsize * n
    flops = 2.0 * heads * (r_kv + d_r + r_kv) * rows * n
    return {"flops": flops, "bytes": nbytes}


def read(ctx):
    trace, peaks = ctx.get("trace"), ctx.get("peaks")
    runs = readers.module_runs(ctx, PROGRAM)
    if not trace or not peaks or not runs:
        return None
    seconds = sum(s for name, s in trace["ops_s"].items() if name.startswith(PROGRAM) and KERNEL in name)
    steps = [r for r in loops.engine_steps(ctx) if r["live"] and r.get("kv_blocks")]
    if not seconds or not steps:
        return None
    need = latent_attention_need(ctx["model"], arith.mean([r["kv_blocks"] for r in steps]),
                                 ctx["engine"]["block_size"], arith.mean([r["live"] for r in steps]))
    least = rooflines.least_time_s(need["flops"], need["bytes"], peaks)
    return 100.0 * least["seconds"] / (seconds / runs[0])
