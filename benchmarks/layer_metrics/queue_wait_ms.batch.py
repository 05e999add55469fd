"""Engine: mean ``llm.queue_wait`` of the requests admitted in the window
(submit to popped from the waiting queue), from the engine's request
records; moves ``serve_tokens_per_s``. In this cell's closed loop (12
callers on 8 slots) four requests always wait, so the number is four slot
turnovers (about 0.75 s each): it follows how fast slots free, not how the
engine admits. Admission shows in a cell with arrivals, not here."""

from benchmarks.harness import loops


def read(ctx):
    return loops.queue_wait_ms(ctx)
