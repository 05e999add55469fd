"""Device programs: seconds the process spent tracing programs and lowering
them to MLIR before the window (``compile`` records of stage ``trace`` or
``lower``, the engine's or the trainer's): what the persistent compile cache
does not skip. Moves ``setup_s``."""

from benchmarks.harness import start


def read(ctx):
    return start.compile_seconds(ctx, ("trace", "lower"))
