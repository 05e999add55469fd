"""The benchmark's own weights, made on the device from ``--seed`` in one
jitted call, in the type they are served in. The program gets them through
its own hooks (``LLMServer(params_loader=...)``, the trainer's state), the
plain reference gets the same arrays: nothing the reference reads was made by
the program. The dict's layout (names, stacked shapes) is the program's
interface; the scales are the usual 1/sqrt(fan-in), with the two projections
that write into the residual stream scaled down by sqrt(2 x layers).
"""

from __future__ import annotations

import math


def seed_words(seed: int):
    """Any whole number (the driver's seeds pass 2**31) as two uint32 words,
    to be passed to the jitted ``make_weights`` as an argument: a seed that
    is traced in as a constant makes another program, and another compile,
    of every seed."""
    import numpy as np

    seed = int(seed)
    return np.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], dtype=np.uint32)


def model_dims(model: dict) -> dict:
    d, h = model["d_model"], model["n_heads"]
    return dict(L=model["n_layers"], D=d, H=h, Hd=d // h, F=model["d_ff"], V=model["vocab_size"])


def make_weights(words, model: dict, dtype):
    """``words`` is ``seed_words(seed)``. Traceable: call under ``jax.jit``
    with ``words`` as its argument (eagerly, each stacked tensor would exist
    in float32 first: 7.5 GB for one MLP tensor of GPT-J-6B)."""
    import jax
    import jax.numpy as jnp

    m = model_dims(model)
    L, D, H, Hd, F, V = m["L"], m["D"], m["H"], m["Hd"], m["F"], m["V"]
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), words[0]), words[1])
    keys = jax.random.split(key, 8)

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)

    s_in, s_ff, s_res = 1 / math.sqrt(D), 1 / math.sqrt(F), 1 / math.sqrt(2 * L)
    return {
        "embed": normal(keys[0], (V, D), 0.02),
        "wq": normal(keys[1], (L, D, H, Hd), s_in),
        "wk": normal(keys[2], (L, D, H, Hd), s_in),
        "wv": normal(keys[3], (L, D, H, Hd), s_in),
        "wo": normal(keys[4], (L, H, Hd, D), s_in * s_res),
        "attn_norm": jnp.ones((L, D), jnp.float32),
        "mlp_norm": jnp.ones((L, D), jnp.float32),
        "w_up": normal(keys[5], (L, D, F), s_in),
        "w_down": normal(keys[6], (L, F, D), s_ff * s_res),
        "final_norm": jnp.ones((D,), jnp.float32),
        "unembed": normal(keys[7], (D, V), s_in),
    }
