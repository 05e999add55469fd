"""Mamba-2's state-space layer over a whole prompt, in chunks (the SSD form:
Dao, Gu, arXiv:2405.21060): the prefill's form of the recurrence that
``ops/selective_scan.py`` steps a token at a time.

A head ``h`` keeps a state ``S`` (N x P), float32. A token with the head's
input ``x`` (P), its step ``dt`` (positive), the head's ``A`` (negative), and
its group's input and output maps ``B``, ``C`` (N each; a group is H / G heads):

    S_t = exp(dt_t A) S_{t-1} + B_t (dt_t x_t)^T;    y_t = S_t^T C_t

With ``cum`` the running sum of ``dt A`` inside a chunk of ``chunk`` positions
and ``L_ij = exp(cum_i - cum_j)`` for i >= j, else 0 (the product of the decays
after j up to i), a chunk that meets the state ``S_0`` gives, in matrix products,

    Y   = ((C B^T) . L) (dt X)  +  exp(cum) . (C S_0)
    S_1 = exp(cum_last) S_0 + (B . exp(cum_last - cum))^T (dt X)

``C B^T`` is a group's, ``L`` a head's. Between chunks the state is carried
from an empty one. **A padded position passes the state through**: its ``dt``
is 0, so its decay is exp(0) = 1 and its input 0; a chunk of padding alone
leaves the state bit for bit. Float32, contractions at the highest precision:
the state outlives the prompt by a thousand steps. Every exponent is a
difference of running sums of non-positive numbers in the order that makes it
non-positive, so nothing overflows whatever the prompt.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CHUNK = 128  # the published ``mamba_chunk_size``
HIGHEST = jax.lax.Precision.HIGHEST


def ssd_chunked(x, dt, a, bm, cm, chunk: int = CHUNK):
    """A prompt from an empty state. ``x`` (B, S, H, P); ``dt`` (B, S, H)
    float32, 0 at every padded position; ``a`` (H,) float32, negative; ``bm``,
    ``cm`` (B, S, G, N), head ``h`` of group ``h // (H / G)``. S a multiple of
    ``chunk`` or less than it. -> (y (B, S, H, P) float32, the state after the
    last position (B, N, H x P) float32: the state dimension in the sublanes,
    every head's channels side by side in the lanes, as the engine's pool and
    ``selective_scan_update`` keep it)."""
    b, s, heads, p = x.shape
    groups, n = bm.shape[2:]
    c, r = min(chunk, s), heads // groups
    if s % c or heads % groups:
        raise ValueError(f"a prompt of {s} positions is not whole chunks of {c}, or {heads} heads not whole groups of {groups}")
    z = s // c
    dot = functools.partial(jnp.einsum, precision=HIGHEST)
    dt = dt.astype(jnp.float32)

    def chunks(t):  # (B, S, G, ..., W) -> (Z, B, G, ..., C, W): a chunk's positions and a width in the tiles
        t = t.astype(jnp.float32).reshape(b, z, c, *t.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(t, 2, -2), 1, 0)

    cum = jnp.cumsum(chunks((dt * a).reshape(b, s, groups, r, 1))[..., 0], axis=-1)  # (Z, B, G, R, C)
    xd = chunks((x.astype(jnp.float32) * dt[..., None]).reshape(b, s, groups, r, p))  # (Z, B, G, R, C, P)
    bm, cm = chunks(bm), chunks(cm)  # (Z, B, G, C, N)
    lower = jnp.tril(jnp.ones((c, c), bool))
    within = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :], -jnp.inf))  # L: (Z, B, G, R, i, j)
    within = within * dot("zbgin,zbgjn->zbgij", cm, bm)[:, :, :, None]
    y_own = dot("zbgrij,zbgrjp->zbgrip", within, xd)
    last = cum[..., -1:]
    fresh = dot("zbgjn,zbgrjp->zbgrnp", bm, xd * jnp.exp(last - cum)[..., None])

    def one(state, xs):  # state (B, G, R, N, P): as the chunk meets it
        cm, cum, fresh = xs
        y = dot("bgin,bgrnp->bgrip", cm, state) * jnp.exp(cum)[..., None]
        return jnp.exp(cum[..., -1])[..., None, None] * state + fresh, y

    state, y_met = jax.lax.scan(one, jnp.zeros((b, groups, r, n, p), jnp.float32), (cm, cum, fresh))
    y = jnp.moveaxis(y_own + y_met, (0, 4), (1, 2)).reshape(b, s, heads, p)  # (Z, B, G, R, C, P) -> (B, Z, C, G, R, P)
    return y, jnp.moveaxis(state, 3, 1).reshape(b, n, heads * p)
