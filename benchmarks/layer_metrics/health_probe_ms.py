"""Entry: the longest round trip of the controller's health probes of the
window (``serve_probe``: ``t_answered - t_sent`` around ``check_health``), the
probes sent before the window's end and at most their budget before its start;
a probe that was not answered reads its budget (``budget_s`` x 1,000), and one
such miss takes the replica out of its handle. Moves ``serve_tokens_per_s``."""

from benchmarks.harness import streams


def read(ctx):
    return streams.health_probe_ms(ctx)
