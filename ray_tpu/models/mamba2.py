"""The Mamba-2 mixer (Dao, Gu, arXiv:2405.21060) of a paged program: the one
function the kinds with such a layer call (``models/falcon_h1.py``: 32 heads of
128 in two groups beside attention in every layer, under µP multipliers;
``models/granite_hybrid.py``: 128 heads of 64 in one group, nine layers in ten,
no multiplier inside the mixer; ``models/nemotron_h.py``: 128 heads of 64 in
eight groups, a layer's only part, five layers in eleven: B and C 1,024 values
each, the convolution over 10,240 channels, the gated norm over eight groups of
1,024). A change here is judged at one, two and eight groups at once. ``u`` is
the layer's normed input:

    [z | x | B | C | dt] = (W_in u) * scales, widths d_ssm, d_ssm, G N, G N, H
    [x | B | C] <- silu(b_conv + causal depthwise convolution of width K)
    dt_h = softplus(dt_h + dt_bias_h);  A_h = -exp(A_log_h)
    head h = P channels of x; group g(h) = h // (H / G) gives B_g, C_g
    S_h <- exp(dt_h A_h) S_h + dt_h x_h B_g^T         S_h: P x N, float32
    y_h = S_h C_g + D_h x_h
    y <- w * RMSNorm_group(y * silu(z))     the gate first, then a norm over
         each of the G groups' d_ssm / G channels
    W_out y

**What the pool holds for it**, a row a sequence (``state`` (mixer layers, state
rows, N, d_ssm) float32: the state dimension in the sublanes, every head's
channels side by side in the lanes; ``conv`` (.., K x (d_ssm + 2 G N)) in the
served type; ``state_pos``: the positions a row has consumed), as
``models/olmo_hybrid.py`` keeps its own and under its rule for a decode step
dispatched twice at one position. A decode step updates the state in place
(``ops/selective_scan.py:selective_scan_update``, its decays given a channel: a
head's number repeated over its P channels, so a head of half a lane tile is the
same call); a prefill computes it in chunks of matrix products
(``ops/ssd.py``) from an empty state, ``x`` and ``y`` (positions, d_ssm) and the
state (N, d_ssm) as the pool keeps it, in chunks of that module's own tile: the
published ``mamba_chunk_size`` is the upstream kernel's tile, defines no
mathematics and is not read here.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ray_tpu.ops import selective_scan
from ray_tpu.ops.gated_delta import short_conv_step
from ray_tpu.ops.layers import rms_norm
from ray_tpu.ops.ssd import ssd_chunked


class Mamba2(NamedTuple):
    """A mixer's numbers, as the published keys name them (``mamba_*``)."""

    d_ssm: int
    d_state: int
    d_head: int
    n_heads: int
    n_groups: int
    d_conv: int
    eps: float
    dtype: Any

    bc_dim = property(lambda self: self.n_groups * self.d_state)  # values of a token's B (or C)
    conv_dim = property(lambda self: self.d_ssm + 2 * self.bc_dim)  # channels the short convolution runs over
    in_dim = property(lambda self: self.d_ssm + self.conv_dim + self.n_heads)  # [z | x | B | C | dt]


def init_pool(m: Mamba2, layers: int, state_rows: int) -> dict:
    """The mixers' part of a pool: ``layers`` of them, ``state_rows`` rows each
    (the null row counted)."""
    return {
        "state": jnp.zeros((layers, state_rows, m.d_state, m.d_ssm), jnp.float32),
        "conv": jnp.zeros((layers, state_rows, m.d_conv * m.conv_dim), m.dtype),
        "state_pos": jnp.zeros((layers, state_rows), jnp.int32),
    }


def state_bytes(m: Mamba2) -> int:
    """Bytes one state row holds of one mixer: the float32 state, the
    convolution's window and the position count."""
    return m.d_state * m.d_ssm * 4 + m.d_conv * m.conv_dim * jnp.dtype(m.dtype).itemsize + 4


def mixer(m: Mamba2, w, u, pool, index, step, scales=None):
    """The mixer whose tensors ``w(name)`` reads (``ssm_in``, ``ssm_conv``,
    ``ssm_conv_b``, ``ssm_dt_b``, ``ssm_a_log``, ``ssm_d``, ``ssm_norm``,
    ``ssm_out``) and whose rows are the pool's layer ``index`` (traced), over
    ``u`` (B, S, D) at ``step`` (``paged.Step``): (out (B, S, D), pool).
    ``scales``: (in_dim,) float32 over the input projection's outputs, or
    none."""
    d_ssm, N, P, Hs, Gs, K = m.d_ssm, m.d_state, m.d_head, m.n_heads, m.n_groups, m.d_conv
    conv_dim, bc, dtype = m.conv_dim, m.bc_dim, m.dtype
    b, s = step.positions.shape
    rows, live = step.state_rows, step.live.reshape(b, s)
    decode = s == 1
    dot32 = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)
    over = functools.partial(jnp.repeat, repeats=P, axis=-1)  # a head's number over its channels
    scan_kernel = decode and selective_scan.can_use_selective_scan_kernel(d_ssm, N)
    with jax.named_scope("proj"):
        proj = dot32("bsd,dc->bsc", u, w("ssm_in"))
        z, xbc, dt = jnp.split(proj if scales is None else proj * scales, [d_ssm, d_ssm + conv_dim], axis=-1)
        xbc = xbc.astype(dtype)
    taps, bias = w("ssm_conv"), w("ssm_conv_b")
    if decode:
        # who holds which row, the position each row's sequence is at, which rows take this step
        # (``models/olmo_hybrid.py``: a step dispatched twice at one position)
        seen = pool["state_pos"][index]
        owner = (rows[None, :] == jnp.arange(len(seen))[:, None]) & live[None, :, 0]
        at_row = jnp.sum(jnp.where(owner, step.positions[None, :, 0], 0), axis=1)
        advance_rows = jnp.any(owner, axis=1) & (seen == at_row)
        seen = jnp.where(advance_rows, at_row + 1, seen)
        advance = jnp.any(owner & advance_rows[:, None], axis=0)
        with jax.named_scope("conv"):
            c, windows = short_conv_step(pool["conv"][index], xbc[:, 0], taps, owner, advance_rows, bias)
            c, windows = c[:, None], pool["conv"].at[index].set(windows)
    else:
        length = jnp.sum(live, axis=1)
        with jax.named_scope("conv"):
            padded = jnp.pad(xbc, ((0, 0), (K, 0), (0, 0)))
            # position t at index t + K: its K inputs are indices t + 1 .. t + K
            c = jax.nn.silu(bias + sum(padded[:, 1 + j:1 + j + s].astype(jnp.float32) * taps[j].astype(jnp.float32)
                                       for j in range(K)))
            # the last K inputs of the real tokens: zeros before the sequence's start
            last = jax.vmap(lambda p, n: jax.lax.dynamic_slice_in_dim(p, n, K, axis=0))(padded, length)
            windows = pool["conv"].at[index, rows].set(last.reshape(b, K * conv_dim))
    with jax.named_scope("gates"):
        xs, bm, cm = jnp.split(c, [d_ssm, d_ssm + bc], axis=-1)
        bm, cm = bm.reshape(b, s, Gs, N), cm.reshape(b, s, Gs, N)
        dt = jax.nn.softplus(dt + w("ssm_dt_b"))
        dt = jnp.where(live[..., None], dt, 0.0)  # a padded position passes the state through
        a = -jnp.exp(w("ssm_a_log").astype(jnp.float32))  # (Hs,)
    if decode:
        with jax.named_scope("update"):
            decay, dl = over(jnp.exp(dt[:, 0] * a)), over(dt[:, 0])
            if scan_kernel:
                y, states = selective_scan.selective_scan_update(
                    pool["state"], index, rows, advance, xs[:, 0], dl, bm[:, 0], cm[:, 0], decay=decay)
            else:
                _, new = selective_scan.ssm_step(pool["state"][index, rows], xs[:, 0], dl, bm[:, 0], cm[:, 0], None,
                                                 advance, decay=decay)
                states = pool["state"].at[index, rows].set(new)
                y = selective_scan.ssm_read(states[index, rows], cm[:, 0])  # from the state as stored, as a replay reads it
            y, positions_seen = y[:, None], pool["state_pos"].at[index].set(seen)
    else:
        with jax.named_scope("scan"):
            y, new = ssd_chunked(xs.reshape(b, s, Hs, P), dt, a, bm, cm)
            y, states = y.reshape(b, s, d_ssm), pool["state"]
            for i in range(b):  # a prompt a call: each row's state written where it lies
                states = jax.lax.dynamic_update_slice(states, new[i][None, None], (index, rows[i], 0, 0))
        positions_seen = pool["state_pos"].at[index, rows].set(length.astype(jnp.int32))
    pool = {**pool, "state": states, "conv": windows, "state_pos": positions_seen}
    with jax.named_scope("gate"):
        y = (y + over(w("ssm_d")) * xs) * jax.nn.silu(z)
        y = rms_norm(y.reshape(b, s, Gs, d_ssm // Gs), w("ssm_norm").reshape(Gs, -1), m.eps).reshape(b, s, d_ssm)
        return y.astype(dtype) @ w("ssm_out"), pool
