"""Device programs: held experts that got at least one row, a layer a decode
step, over the window: what a step reads of its expert weights (the family's
``decode_step_need`` counts on ``experts_touched(model, batch)`` of them). From
the engine's ``llm_moe`` loop records (cumulative counts the expert layers
sum on the device, read once a flush interval): the difference between the
first and the last record of the window over the decode steps between them.
None on a program that writes no such records; moves ``serve_tokens_per_s``."""

from benchmarks.harness import loops


def read(ctx):
    t0, t1 = (int(t * 1e9) for t in ctx["window"])
    recs = [r for r in loops.load("llm-", "llm_moe") if t0 <= r["t"] < t1]
    if len(recs) < 2:
        return None
    first, last = recs[0], recs[-1]
    layer_steps = (last["step"] - first["step"]) * last["layers"]
    return (last["touched"] - first["touched"]) / layer_steps if layer_steps > 0 else None
