"""Device programs: seconds of backend compilation before the window
(``compile`` records of stage ``compile``): in a run over a warm compile cache
the loads from it (``cache_load`` records lie inside these seconds and are not
added), in a first run the compiler's own time. Moves ``setup_s``."""

from benchmarks.harness import start


def read(ctx):
    return start.compile_seconds(ctx, ("compile",))
