"""Entry: seconds from the top of ``LLMServer.__init__`` until the platform is
chosen and the first device is in hand (``llm_start``: ``t_backend - t_init``):
the import of jax and the chip's start. Moves ``setup_s``."""

from benchmarks.harness import start


def read(ctx):
    return start.phase_s("t_init", "t_backend")
