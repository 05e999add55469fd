"""Entry: a token's mean wait in its stream's queue, from the loop's ``put``
until ``get`` returns on the stream's own thread: GIL turns among the replica's
threads, and the thread still busy sending the token before. From the
``llm_stream`` records of the streams that ended in the window: sum of
``wake_sum`` over sum of ``wake_n``. Moves ``serve_tokens_per_s``."""

from benchmarks.harness import streams


def read(ctx):
    return streams.engine_segment_ms(ctx, "wake")
