"""Entry: seconds from the start of ``run.py`` to the first line of the
program's own start: the top of ``LLMServer.__init__`` (``llm_start.t_init``)
in a serving cell, the ``StepTimer``'s creation (step 0's ``t0_ns``) in the
training cell. Cluster, serve controller and proxy or the trainer's executor,
worker spawn, imports. Moves ``setup_s``."""

from benchmarks.harness import start


def read(ctx):
    return start.process_s(ctx)
