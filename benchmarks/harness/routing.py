"""What the readers of the expert layers' routing counts share: the engine's
``llm_moe`` loop records (``looplog.LLM_MOE_FIELDS``: cumulative counts the
decode steps' expert layers sum on the device, read once a flush interval) over
the window."""

from __future__ import annotations

from benchmarks.harness import loops


def window_counts(ctx, *fields):
    """What the decode steps between the window's first and last ``llm_moe``
    record added to each of ``fields``, and how many expert layers ran there
    (decode steps x the model's expert layers): ``(deltas, layer_steps)``.
    None without two records that carry every field (a program that writes
    none, or an older one that lacks a count)."""
    t0, t1 = (int(t * 1e9) for t in ctx["window"])
    recs = [r for r in loops.load("llm-", "llm_moe") if t0 <= r["t"] < t1 and all(f in r for f in fields)]
    if len(recs) < 2:
        return None
    first, last = recs[0], recs[-1]
    layer_steps = (last["step"] - first["step"]) * last["layers"]
    if layer_steps <= 0:
        return None
    return [last[f] - first[f] for f in fields], layer_steps
