"""Entry: a token's mean time in its stream's thread from ``get``'s return
until the thread is back for the next: the generators above the engine's, the
runtime's ``serialize_to_bytes`` and ``conn.send``. From the ``llm_stream``
records of the streams that ended in the window: sum of ``send_sum`` over sum of
``send_n``. Moves ``serve_tokens_per_s``."""

from benchmarks.harness import streams


def read(ctx):
    return streams.engine_segment_ms(ctx, "send")
