"""Engine: seconds from the backend up until the pool is committed
(``llm_start``: ``t_pool - t_backend``): the weights made or loaded and on the
device, the stacked tensors placed, the pool allotted. The programs compiled in
it are in ``start_lowering_s`` and ``start_compile_s`` too. Moves ``setup_s``."""

from benchmarks.harness import start


def read(ctx):
    return start.phase_s("t_backend", "t_pool")
