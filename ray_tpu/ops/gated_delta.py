"""The gated delta rule (Gated DeltaNet: Yang, Kautz, Hatamizadeh,
arXiv:2412.06464): a linear-attention layer's recurrent state and its three
forms here.

A head keeps a matrix ``S`` (d_k x d_v). A token with query ``q``, key ``k``
(both L2-normalised, ``q`` also scaled by ``d_k^-1/2``), value ``v``, decay
``alpha = exp(g)`` in (0, 1] and writing strength ``beta`` in (0, 2):

    S' = alpha S;  u = beta (v - S'^T k);  S_t = S' + k u^T;  o = S_t^T q

* ``gated_delta_step``: that, for B rows, in ``jax.numpy``. A decode step off
  the TPU and the tests' statement of the rule.
* ``gated_delta_update``: the same arithmetic as a Pallas TPU kernel over the
  engine's state pool: each row's state is read where it lies, once, and written
  back in place, once (``input_output_aliases``); the row's index and the
  layer's are scalars prefetched for the block's index map.
* ``short_conv_step``: the layer's short convolution for a decode step, over
  a window of the last K inputs a sequence, under the same ``advance``.
* ``gated_delta_chunked``: a whole prompt in chunks of ``CHUNK`` tokens (the
  WY form: inside a chunk the updates are solved as one unit-triangular system,
  between chunks the state is carried). It is the prefill's, held to the token
  recurrence by a test.
* ``unit_lower_solve``: that system's solve, forward substitution by blocks of
  ``SOLVE_BLOCK`` rows as batched float32 products over every chunk, row and
  head at once (PR 48: block rows on the right-hand side were kept, the whole
  inverse built by halves was as fast on the chip and read 3 to 5 times the
  error on the CPU; no power of ``L`` is formed). One path on every platform.

**How a state lies in memory.** (d_k, H x d_v), float32: the key dimension in
the sublanes and every head's values side by side in the lanes. A head's own
(96, 192) matrix would have its 192 values padded to 256 lanes by the device's
tiling (a third more memory and traffic); 30 heads x 192 is 45 whole lane
tiles. The kernel walks the lanes in groups of whole tiles (two heads of 192)
and never cuts a tile.

**A step may be replayed at its position.** ``advance`` (B,) says whether a row
takes the update; where it is false the state stays as stored and ``o`` is read
from the stored state, which is what the update had left there: the same
``o``, bit for bit (``models/olmo_hybrid.py`` says who needs that).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
SOLVE_BLOCK = 16  # rows of a diagonal block of a chunk's unit-triangular system (``unit_lower_solve``)
HIGHEST = jax.lax.Precision.HIGHEST
_L2_EPS = 1e-6
_LANES = 128
_VMEM_LIMIT = 48 << 20  # two buffers in, two out, of a whole row's state (2.2 MB at the published widths)


def l2_normalise(x, scale: float = 1.0):
    """``x / ||x||_2 x scale`` over the last axis, float32 (``_L2_EPS`` under
    the root, as the published kernels have it)."""
    x = x.astype(jnp.float32)
    return x * (jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS) * scale)


def decay_and_strength(a, b, a_log, dt_bias, allow_neg_eigval: bool = True):
    """The two gates of a token from their projections ``a``, ``b`` (..., H):
    ``g = -exp(A_log) softplus(a + dt_bias)`` (the log of the decay, <= 0) and
    ``beta = sigmoid(b)``, doubled where the state's eigenvalues may be
    negative. Float32."""
    g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(a.astype(jnp.float32) + dt_bias.astype(jnp.float32))
    beta = jax.nn.sigmoid(b.astype(jnp.float32))
    return g, beta * 2.0 if allow_neg_eigval else beta


def gated_delta_read(state, q):
    """``o = S^T q`` a head: ``state`` (B, d_k, H x d_v) float32, ``q`` (B, H,
    d_k) as it leaves the short convolution (normalised here) -> (B, H, d_v)
    float32. Products and sums elementwise: no matmul whose precision a
    backend may choose."""
    b, heads, d_k = q.shape
    qt = jnp.swapaxes(l2_normalise(q, d_k ** -0.5), 1, 2)[..., None]  # (B, d_k, H, 1)
    return jnp.sum(state.reshape(b, d_k, heads, -1) * qt, axis=1)


def gated_delta_step(state, q, k, v, g, beta, advance=None):
    """One token a row. ``state`` (B, d_k, H x d_v) float32; ``q``, ``k`` (B,
    H, d_k) as they leave the short convolution (normalised here); ``v`` (B,
    H, d_v); ``g``, ``beta`` (B, H) from ``decay_and_strength``; ``advance``
    (B,) bool or None (every row). -> (o (B, H, d_v) float32, the new state).
    A caller that must get the same ``o`` from an update and from its replay
    reads it with ``gated_delta_read`` from the state *as stored*: inside one
    program a compiler may compute the new state a second time for ``o``,
    fused another way, a last place off what it stores (XLA's CPU backend
    does, and drops an optimization barrier put in its way)."""
    b, heads, d_k = q.shape
    d_v = v.shape[-1]
    advance = jnp.ones((b,), bool) if advance is None else advance
    kt = jnp.swapaxes(l2_normalise(k), 1, 2)[..., None]  # (B, d_k, H, 1)
    old = state.reshape(b, d_k, heads, d_v)
    decayed = old * jnp.exp(g)[:, None, :, None]
    u = beta[..., None] * (v.astype(jnp.float32) - jnp.sum(decayed * kt, axis=1))
    new = jnp.where(advance[:, None, None, None], decayed + kt * u[:, None], old).reshape(state.shape)
    return gated_delta_read(new, q), new


# -- the kernel ------------------------------------------------------------------------


def _lane_group(heads: int, d_v: int) -> int:
    """Heads a group of whole lane tiles holds: the fewest whose values fill
    tiles exactly; 0 where no number of this model's heads does."""
    g = _LANES // math.gcd(d_v, _LANES)
    return g if heads % g == 0 else 0


def can_use_gated_delta_kernel(heads: int, d_k: int, d_v: int) -> bool:
    """Platform and static shape alone, as ``can_use_paged_kernel``: a TPU, keys
    of whole sublane tiles and heads that pair up into whole lane tiles."""
    return jax.default_backend() == "tpu" and d_k % 8 == 0 and _lane_group(heads, d_v) > 0


def _update_kernel(li_ref, rows_ref, adv_ref, qt_ref, kt_ref, v_ref, alpha_ref, beta_ref, s_ref, o_ref, s_out, *,
                   d_v, group):
    """One row's state (d_k, H x d_v) a grid step, a group of heads at a time:
    the group's lanes of the state against ``k`` and ``q`` spread over those
    lanes (column ``h`` of (d_k, H) to head ``h``'s ``d_v`` lanes)."""
    del li_ref, rows_ref
    advance = adv_ref[pl.program_id(0)] != 0
    d_k, heads = kt_ref.shape[-2:]
    width = group * d_v
    lane = jax.lax.broadcasted_iota(jnp.int32, (d_k, width), 1)

    def spread(ref, first):
        out = jnp.broadcast_to(ref[0, :, first:first + 1], (d_k, width))
        for j in range(1, group):
            out = jnp.where(lane >= j * d_v, ref[0, :, first + j:first + j + 1], out)
        return out

    for first in range(0, heads, group):
        at = pl.ds(first * d_v, width)
        state = s_ref[0, 0, :, at]
        k, q = spread(kt_ref, first), spread(qt_ref, first)
        decayed = state * alpha_ref[0, :, at]
        u = beta_ref[0, :, at] * (v_ref[0, :, at] - jnp.sum(decayed * k, axis=0, keepdims=True))
        new = jnp.where(advance, decayed + k * u, state)
        s_out[0, 0, :, at] = new
        o_ref[0, :, at] = jnp.sum(new * q, axis=0, keepdims=True)


def gated_delta_update(pool, layer, rows, advance, q, k, v, g, beta, *, interpret=False):
    """``gated_delta_step`` over the state pool where it lies. ``pool``
    (layers, rows, d_k, H x d_v) float32; ``layer`` (traced) and ``rows`` (B,)
    name each sequence's state, ``advance`` (B,) bool as above; the rest as
    ``gated_delta_step`` takes them. -> (o (B, H, d_v) float32, the pool, the
    rows named updated in place; every other row untouched). Rows that several
    sequences name (the null row of inactive slots) must not advance."""
    b, heads, d_k = q.shape
    d_v = v.shape[-1]
    group = _lane_group(heads, d_v) or heads  # the interpreter cuts lanes anywhere
    wide = heads * d_v

    def lanes(x):  # (B, H) -> (B, 1, H x d_v): a head's number over its lanes
        return jnp.repeat(x.astype(jnp.float32), d_v, axis=-1)[:, None, :]

    qt = jnp.swapaxes(l2_normalise(q, d_k ** -0.5), 1, 2)  # (B, d_k, H)
    kt = jnp.swapaxes(l2_normalise(k), 1, 2)
    row = pl.BlockSpec((1, 1, wide), lambda i, *_: (i, 0, 0))
    cols = pl.BlockSpec((1, d_k, heads), lambda i, *_: (i, 0, 0))
    state = pl.BlockSpec((1, 1, d_k, wide), lambda i, li, rows, adv: (li[0], rows[i], 0, 0))
    o, pool = pl.pallas_call(
        functools.partial(_update_kernel, d_v=d_v, group=group),
        out_shape=(jax.ShapeDtypeStruct((b, 1, wide), jnp.float32), jax.ShapeDtypeStruct(pool.shape, pool.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b,),
            in_specs=[cols, cols, row, row, row, state], out_specs=(row, state),
        ),
        input_output_aliases={8: 1},  # the pool, counted with the three prefetched scalars
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT),
        name="gated_delta_update",
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), rows.astype(jnp.int32), advance.astype(jnp.int32),
        qt, kt, v.astype(jnp.float32).reshape(b, 1, wide), lanes(jnp.exp(g)), lanes(beta), pool,
    )
    return o.reshape(b, heads, d_v), pool


# -- the short convolution's window, a token a row ------------------------------------------


def short_conv_step(windows, u, weights, owner, advance, bias=None, activation=jax.nn.silu):
    """One token a sequence through the short convolution, over **all** of a
    layer's windows at once. ``windows`` (R, K x C) in the served type: a row's
    last K inputs, the oldest first, flat in the lanes (shifting one in is a
    move of whole lane tiles where C is whole tiles); ``u`` (B, C) the tokens'
    projections; ``weights`` (K, C); ``owner`` (R, B) bool: sequence ``b``
    holds row ``r`` (an inactive slot holds none); ``advance`` (R,) bool: where
    it is false the window stays as stored, which already holds this token.
    ``bias`` (C,) or None: added to the taps' sum before the SiLU (a
    state-space layer's convolution has one, a gated delta-rule layer's none).
    ``activation``: what the sum goes through (None: nothing, where the
    convolution is the whole mixer and gated outside: ``models/lfm2_moe.py``).
    -> (silu(sum_j w_j u_{t-K+1+j}) (B, C) float32, the new windows).

    Dense on purpose: a layer's windows are a few megabytes, and rows move
    between the pool's order and the batch's through two products with
    ``owner`` (exact: one 1 a column) where a gather and a scatter of B rows
    were B copies each, one after the other (0.27 ms a layer for 9 MB of
    traffic, PERF.md section 6, PR 36)."""
    k, c = weights.shape
    moves = owner.astype(windows.dtype)
    dot = functools.partial(jnp.einsum, precision=HIGHEST, preferred_element_type=jnp.float32)
    arrived = dot("rb,bc->rc", moves, u.astype(windows.dtype)).astype(windows.dtype)
    windows = jnp.where(advance[:, None], jnp.concatenate([windows[:, c:], arrived], axis=-1), windows)
    mine = dot("rb,rw->bw", moves, windows)  # (B, K x C) float32, each a stored window's values
    taps = sum(mine[:, j * c:(j + 1) * c] * weights[j].astype(jnp.float32) for j in range(k))
    if bias is not None:
        taps = taps + bias.astype(jnp.float32)
    return (activation(taps) if activation else taps), windows


# -- a prompt, in chunks ----------------------------------------------------------------


def unit_lower_solve(lower, rhs):
    """``X`` of ``(I + L) X = rhs`` for ``lower`` = ``L`` (..., c, c), strictly
    lower triangular, and ``rhs`` (..., c, d): forward substitution by blocks
    of ``SOLVE_BLOCK`` rows, every system at once, float32.

    Two phases. The first inverts every diagonal block ``I + L_ii`` by the
    substitution written out, row ``r`` of the inverse from the rows above it:
    a loop of ``SOLVE_BLOCK`` steps with the blocks of all systems side by side
    in the lanes (a (16, 16) block a system would fill an eighth of its
    tiles). The second takes block row ``i`` of ``X`` as ``inv(I + L_ii)
    (rhs_i - L_i,<i X_<i)``: two batched products at the highest precision a
    block row, ``c / SOLVE_BLOCK`` deep, written out. A ``c`` that is not whole
    blocks (the tests' 24 and 20 with blocks of 16; a power of two is nothing
    special here) is padded with rows of the identity, which solve to the
    zeros they are handed and are cut off again.

    No power of ``L`` is formed and no inverse wider than a block: with
    ``beta`` near 2 and a chunk's keys nearly parallel ``L`` is ~2 everywhere
    under its diagonal, its powers grow by binomials (past 1e20) while the
    solution stays ~10, and the product form ``(I - L)(I + L^2)(I + L^4)..``
    cancels to nothing in float32 (NaN on the tests' adversarial case); the
    whole inverse built by halves (``inv [[A, 0], [C, B]] = [[inv A, 0],
    [-inv B C inv A, inv B]]``, 16 to 64) reads 3 to 5 times this form's error
    there (``tests/test_gated_delta.py`` has every reading).

    PR 48, on the chip (TPU v5 lite; 240 systems of 64 x 64 against 288
    columns, the hybrid's 512 bucket; device time of ``gated_delta_chunked``
    whole, us a call): ``jax.lax.linalg.triangular_solve``, which the TPU's
    compiler lowers to ``InvertDiagBlocksLowerTriangular`` (a walk a row at a
    time over each 64 x 64 block), 1,606.8; this form 491.4; the inverse by
    halves 495.1 from diagonal blocks of 16 and 528.8 from 8; block rows of 8
    614.5, of 32 649.9. Which phase is a loop was measured too: with the first
    written out as well 494.4, and 373 more operations a prefill program,
    3.5 s more of every replica's start; with the second a loop over ``X``
    whole 825.4, its update of ``X`` a copy of all of it a step."""
    c, w = lower.shape[-1], SOLVE_BLOCK
    short = -c % w
    if short:
        batch = [(0, 0)] * (lower.ndim - 2)
        lower = jnp.pad(lower, batch + [(0, short), (0, short)])
        rhs = jnp.pad(rhs, batch + [(0, short), (0, 0)])
    blocks = [slice(i, i + w) for i in range(0, c + short, w)]
    diagonal = jnp.stack([lower[..., at, at] for at in blocks], axis=-3)  # (..., blocks, w, w)
    across = jnp.moveaxis(diagonal.reshape(-1, w, w), 0, -1)  # (w, w, every block of every system): those in the lanes
    eye = jnp.eye(w, dtype=lower.dtype)[..., None]

    def row(r, inverse):  # the inverse's rows from r on are still zero, and so is L's row r there
        return inverse.at[r].set(eye[r] - jnp.sum(across[r][:, None] * inverse, axis=0))

    inverse = jax.lax.fori_loop(0, w, row, jnp.zeros_like(across))
    inverse = jnp.moveaxis(inverse, -1, 0).reshape(diagonal.shape)
    dot = functools.partial(jnp.einsum, "...ij,...jk->...ik", precision=HIGHEST)
    solved = []
    for at in blocks:
        ahead = rhs[..., at, :]
        if solved:
            ahead = ahead - dot(lower[..., at, :at.start], jnp.concatenate(solved, axis=-2))
        solved.append(dot(inverse[..., len(solved), :, :], ahead))
    return jnp.concatenate(solved, axis=-2)[..., :c, :]


def gated_delta_chunked(q, k, v, g, beta, live, chunk: int = CHUNK):
    """A prompt from an empty state. ``q``, ``k`` (B, S, H, d_k) as they leave
    the short convolution, ``v`` (B, S, H, d_v), ``g``, ``beta`` (B, S, H),
    ``live`` (B, S) bool: a padded position passes the state through (decay 1,
    strength 0, so its row of ``L`` is 0). S a multiple of ``chunk`` or less
    than it. -> (o (B, S, H, d_v) float32, the state after the last position
    (B, d_k, H x d_v) float32).

    Inside a chunk, with ``gamma`` the running sum of ``g`` and ``M_ij =
    exp(gamma_i - gamma_j)`` for i >= j: ``L = strict_tril((K beta) K^T . M)``,
    ``U = (I + L)^-1 (V beta)``, ``W = (I + L)^-1 (K beta exp(gamma))``, both
    from one ``unit_lower_solve`` over every chunk, row and head at once, ahead
    of the scan between chunks; with the state ``S_0`` the chunk meets: ``V' =
    U - W S_0``, ``O = (Q exp(gamma)) S_0 + tril(Q K^T . M) V'``, ``S_1 =
    exp(gamma_C) S_0 + (K exp(gamma_C - gamma))^T V'``. Float32, contractions
    at the highest precision: the state outlives the prompt by a thousand
    steps."""
    b, s, heads, d_k = q.shape
    d_v = v.shape[-1]
    c = min(chunk, s)
    if s % c:
        raise ValueError(f"a prompt of {s} positions is not whole chunks of {c}")
    n = s // c
    on = live[..., None]
    g, beta = jnp.where(on, g, 0.0), jnp.where(on, beta, 0.0)

    def chunks(x):  # (B, S, H, ...) -> (N, B, H, C, ...)
        x = x.reshape(b, n, c, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    q, k, v = chunks(l2_normalise(q, d_k ** -0.5)), chunks(l2_normalise(k)), chunks(v.astype(jnp.float32))
    gamma, beta = jnp.cumsum(chunks(g), axis=-1), chunks(beta)  # (N, B, H, C)
    lower = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.exp(jnp.where(lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))  # M
    kb = k * beta[..., None]
    dot = functools.partial(jnp.einsum, precision=HIGHEST)
    strict = jnp.where(jnp.tril(lower, -1), dot("nbhik,nbhjk->nbhij", kb, k) * decay, 0.0)  # L
    rhs = jnp.concatenate([v * beta[..., None], kb * jnp.exp(gamma)[..., None]], axis=-1)
    solved = unit_lower_solve(strict, rhs)
    u, w = solved[..., :d_v], solved[..., d_v:]
    within = dot("nbhik,nbhjk->nbhij", q, k) * decay
    q_in = q * jnp.exp(gamma)[..., None]
    last = gamma[..., -1:]
    k_out = k * jnp.exp(last - gamma)[..., None]

    def one(state, xs):  # state (B, H, d_k, d_v)
        u, w, within, q_in, k_out, carried = xs
        fresh = u - dot("bhck,bhkv->bhcv", w, state)
        o = dot("bhck,bhkv->bhcv", q_in, state) + dot("bhij,bhjv->bhiv", within, fresh)
        return carried[..., None, None] * state + dot("bhck,bhcv->bhkv", k_out, fresh), o

    state, o = jax.lax.scan(one, jnp.zeros((b, heads, d_k, d_v), jnp.float32),
                            (u, w, within, q_in, k_out, jnp.exp(last[..., 0])))
    o = jnp.moveaxis(o, 0, 1).swapaxes(2, 3).reshape(b, s, heads, d_v)  # (N, B, H, C, dv) -> (B, S, H, dv)
    return o, jnp.swapaxes(state, 1, 2).reshape(b, d_k, heads * d_v)
