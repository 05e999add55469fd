"""What several per-layer readers share. A reader gets the run's context
(``counters``, client ``records``, the reduced ``trace``, ``peaks``, the
cell's ``model``/``engine``/``train`` sizes) and returns a number, or None
where it finds nothing to read."""

from __future__ import annotations


def decode_step_ms(ctx):
    """Mean of ``ray_tpu_llm_decode_step_ms`` over the window (host clock in
    the engine, dispatch to ``np.asarray``, which waits for the device)."""
    c = ctx.get("counters") or {}
    if not c.get("decode_steps"):
        return None
    return c["decode_step_ms_sum"] / c["decode_steps"]


def module_runs(ctx, prefix: str):
    """(runs, seconds) of the device programs whose name starts with
    ``prefix`` in the traced window."""
    mods = (ctx.get("trace") or {}).get("modules", {})
    hits = [m for name, m in mods.items() if name.startswith(prefix)]
    runs = sum(m["count"] for m in hits)
    if not runs:
        return None
    return runs, sum(m["total_s"] for m in hits)
