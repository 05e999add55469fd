"""What the readers of a start share: the engine's ``llm_start`` record (the
stamps of a replica's start, ``looplog.LLM_START_FIELDS``) and the ``compile``
records of the engine's or the trainer's file (one a program traced, lowered,
compiled or loaded from the compile cache, ``looplog.COMPILE_FIELDS``), held
against the run's own clock: the window's start ``t0`` and, ``setup_s`` before
it, the start of ``run.py``. On a program that writes no such record every
function returns None."""

from __future__ import annotations

from benchmarks.harness import loops


def window_ns(ctx, where=None):
    """``(t0, t1)`` of the window in ``time.time_ns()``: a serving cell's
    ``ctx["window"]``; in a training cell the first window step's ``t0_ns``
    and the last one's ``t2_ns``. None where the steps cannot be placed."""
    if "window" in ctx:
        return tuple(int(t * 1e9) for t in ctx["window"])
    steps = loops.window_steps(ctx, where)
    return (steps[0]["t0_ns"], steps[-1]["t2_ns"]) if steps else None


def run_start_ns(ctx, where=None):
    window = window_ns(ctx, where)
    return None if window is None else window[0] - int(ctx["e2e"]["setup_s"] * 1e9)


def llm_start(where=None):
    recs = loops.load("llm-", "llm_start", where)
    return recs[0] if recs else None


def phase_s(first: str, last: str, where=None):
    """Seconds between two stamps of the engine's start."""
    st = llm_start(where)
    return None if st is None else (st[last] - st[first]) / 1e9


def process_s(ctx, where=None):
    """From the start of ``run.py`` to the first line of the program's own
    start: ``llm_start.t_init`` in a serving cell, the step plane's first
    ``t0_ns`` (the ``StepTimer``'s creation) in a training cell; there only
    of a program whose trainer writes ``compile`` records, so that a run's
    line holds every part of its start or none."""
    begin = run_start_ns(ctx, where)
    if "window" in ctx:
        first = (llm_start(where) or {}).get("t_init")
    elif not compile_records(ctx, where):
        return None
    else:
        first = min((r["t0_ns"] for r in loops.load("train-", "train_step", where) if r.get("rank") == 0), default=None)
    return None if begin is None or first is None else (first - begin) / 1e9


def compile_records(ctx, where=None) -> list:
    """The ``compile`` records of the cell's loop: the engine's file in a
    serving cell, the trainer's in a training cell."""
    return loops.load("llm-" if "window" in ctx else "train-", "compile", where)


def _before_and_inside(ctx, where=None):
    """The cell's ``compile`` records that ended before the window, and those
    that ended inside it; None without records or without a window."""
    recs, window = compile_records(ctx, where), window_ns(ctx, where)
    if not recs or window is None:
        return None
    return [r for r in recs if r["t"] < window[0]], [r for r in recs if window[0] <= r["t"] < window[1]]


def compile_seconds(ctx, stages, where=None):
    """Seconds of the ``compile`` records of ``stages`` that ended before the window."""
    split = _before_and_inside(ctx, where)
    return None if split is None else sum(r["seconds"] for r in split[0] if r["stage"] in stages)


def compiles_in_window(ctx, where=None):
    """Backend compilations that ended inside the window, as the counters are read."""
    split = _before_and_inside(ctx, where)
    return None if split is None else sum(1 for r in split[1] if r["stage"] == "compile")
