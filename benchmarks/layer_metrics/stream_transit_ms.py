"""Entry: an item's mean time from just before its sender's ``conn.send`` in
the replica until ``ray_tpu.get`` has returned it in the caller's
``DeploymentResponseGenerator`` (two processes of one host, one clock): the
connection, the caller's wake-up, the fetch. It overlaps ``stream_send_ms`` by
the ``conn.send`` call. From the ``serve_stream`` records of the streams that
ended in the window: sum of ``transit_sum`` over sum of ``transit_n``. Moves
``serve_tokens_per_s``."""

from benchmarks.harness import streams


def read(ctx):
    return streams.transit_ms(ctx)
