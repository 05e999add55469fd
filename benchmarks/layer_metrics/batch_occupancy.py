"""Decode slots filled, in percent: tokens that decode steps produced in the
window over (decode steps x max_batch), from the engine's counters. The
engine counts each request's first token (which its prefill produces) under
``decode`` too, so those are taken off."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("decode_steps"):
        return None
    return 100.0 * (c["decode_tokens"] - c["first_tokens"]) / (c["decode_steps"] * c["max_batch"])
