

def block(x, lw, precision):  # noqa: F811 - on purpose: it takes the sound block's place
    """NOT this family's block: GPT-J's residual path on this family's
    layout. The MLP reads the attention's normed input and both join the
    stream together. ``test_families.py`` appends this to the reference's
    text to see `correct` come out false."""
    h = rms_norm(x, lw["attn_norm"])
    return x + attention(h, lw, precision) + mlp(h, lw, precision)
