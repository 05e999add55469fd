"""Device programs: backend compilations that ended inside the measured window
(``compile`` records of stage ``compile``): a shape the warm-up missed, or a
program lowered again. 0 in a sound run. Moves ``serve_tokens_per_s``."""

from benchmarks.harness import start


def read(ctx):
    return start.compiles_in_window(ctx)
