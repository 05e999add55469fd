"""Mamba-2's state-space layer over a whole prompt, in chunks (the SSD form:
Dao, Gu, arXiv:2405.21060): the prefill's form of the recurrence that
``ops/selective_scan.py`` steps a token at a time.

A head ``h`` keeps a state ``S`` (N x P), float32. A token with the head's
input ``x`` (P), its step ``dt`` (positive), the head's ``A`` (negative), and
its group's input and output maps ``B``, ``C`` (N each; a group is H / G heads):

    S_t = exp(dt_t A) S_{t-1} + B_t (dt_t x_t)^T;    y_t = S_t^T C_t

With ``cum`` the running sum of ``dt A`` inside a chunk of ``c`` positions and
``L_ij = exp(cum_i - cum_j)`` for i >= j, else 0 (the product of the decays
after j up to i), a chunk that meets the state ``S_0`` gives, in matrix products,

    Y   = ((C B^T) . L) (dt X)  +  exp(cum) . (C S_0)
    S_1 = exp(cum_last) S_0 + (B . exp(cum_last - cum))^T (dt X)

``C B^T`` is a group's, ``L`` a head's. Between chunks the state is carried
from an empty one. **A padded position passes the state through**: its ``dt``
is 0, so its decay is exp(0) = 1 and its input 0; a chunk of padding alone
leaves the state bit for bit. Float32, contractions at the highest precision
(six bfloat16 passes on the chip, in XLA and in the kernel alike): the state
outlives the prompt by a thousand steps. Every exponent is a difference of
running sums of non-positive numbers in the order that makes it non-positive,
so nothing overflows whatever the prompt.

**Where everything lies: as the state does.** ``q = H x P``, every head's
channels side by side, is the lane dimension of all that is wide: ``x``, ``dt
X`` and ``y`` are (positions, q) from the mixer's convolution to its gate, the
state (N, q) from the first chunk to the engine's pool (``models/mamba2.py``
writes it there as it comes), and a head's decay is one number broadcast over
its P lanes. So the two cross-chunk products are plain matrix products a group,
``B^T`` (N x c) by (c x q) and ``C`` (c x N) by (N x q), and nothing is moved
between (positions, q) and (heads, positions, P): a head of 64 is half a lane
tile, and re-laying ``x``, ``y`` and the state by head was 0.4 of the 0.9 ms a
layer the head-major form took at Granite's 1,024 (PERF.md, PR 56). Only what
is small is laid by head: ``dt`` and ``cum`` (positions x H) and ``C B^T`` (c x
c a chunk and group).

**The tile** (``c`` above) defines no mathematics, only which products carry
the recurrence: the work within chunks grows with it, the number of sequential
chunks falls. It is taken from the static shapes and not from a published
``mamba_chunk_size`` (256 in Granite-4.0-H's file, 128 in Falcon-H1's: the
upstream kernel's tile): ``TILE`` positions where the prompt has as many, the
whole prompt below that (``_tile``).

* On a TPU with whole lane tiles (``can_use_ssd_kernel``) a Pallas kernel over
  (sequence, block of ``_LANE_BLOCK`` lanes, chunk): a head's ``L`` is made in
  fast memory from the chunk's ``cum`` (a column and a row) and never lies in
  HBM, a head of less than a lane tile shares its tile's products under a
  lane mask, and the block's (N, lanes) state is the output block the chunk
  axis revisits.
* Elsewhere the same products in ``jax.numpy``, the within-chunk one batched
  over the heads; its tile is halved until ``L`` (H x S x c float32) is 32 MiB
  at the most.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 128  # positions a chunk: a lane tile, so that ``cum`` as a row and C B^T are whole tiles
HIGHEST = jax.lax.Precision.HIGHEST
_LANES = 128
_LANE_BLOCK = 1024  # lanes of q a grid step holds the state of
_DECAYS_BYTES = 32 << 20  # the most ``L`` may take in HBM where the kernel does not run
_VMEM_LIMIT = 32 << 20  # x, y and the state twice buffered are 3 MB a step at N 256; ``L`` and the products' operands beside


def can_use_ssd_kernel(s: int, heads: int, p: int, groups: int, n: int) -> bool:
    """Platform and static shape alone, as ``can_use_selective_scan_kernel``: a
    TPU, a group's channels and the state dimension whole lane tiles, heads
    that fill lane tiles or share one, a chunk of whole tiles."""
    c = _tile(s)
    return (jax.default_backend() == "tpu" and heads % groups == 0 and (heads // groups * p) % _LANES == 0 and n % _LANES == 0
            and (_LANES % p == 0 or p % _LANES == 0) and (c % _LANES == 0 or (c == s and c % 8 == 0)))


def _tile(s: int) -> int:
    """Positions a chunk of a prompt of ``s``: ``TILE``, a prompt shorter than
    that whole, and where ``TILE`` does not divide a longer one their largest
    common divisor."""
    return s if s <= TILE else math.gcd(s, TILE)


def ssd_chunked(x, dt, a, bm, cm, tile: int | None = None, *, kernel=None, interpret=False):
    """A prompt from an empty state. ``x`` (B, S, H, P); ``dt`` (B, S, H)
    float32, 0 at every padded position; ``a`` (H,) float32, negative; ``bm``,
    ``cm`` (B, S, G, N), head ``h`` of group ``h // (H / G)``. ``tile``: the
    positions a chunk, from the shapes where none is given (module docstring);
    S a multiple of it or less than it. -> (y (B, S, H, P) float32, the state
    after the last position (B, N, H x P) float32: the state dimension in the
    sublanes, every head's channels side by side in the lanes, as the engine's
    pool and ``selective_scan_update`` keep it). Inside, ``x`` and ``y`` are (B,
    S, H x P): the reshapes at both ends cancel against the caller's."""
    b, s, heads, p = x.shape
    groups, n = bm.shape[2:]
    if kernel is None:
        kernel = tile is None and can_use_ssd_kernel(s, heads, p, groups, n)
    if tile is None:
        tile = _tile(s)
        while not kernel and tile % 2 == 0 and b * heads * s * tile * 4 > _DECAYS_BYTES:
            tile //= 2
    c = min(tile, s)
    if s % c or heads % groups:
        raise ValueError(f"a prompt of {s} positions is not whole chunks of {c}, or {heads} heads not whole groups of {groups}")
    dt = dt.astype(jnp.float32)
    cum = jnp.cumsum((dt * a).reshape(b, s // c, c, heads), axis=2)  # (B, Z, C, H): the running sum inside a chunk
    args = (x.astype(jnp.float32).reshape(b, s, heads * p), dt, cum, bm.astype(jnp.float32), cm.astype(jnp.float32))
    y, state = _ssd_call(*args, p=p, interpret=interpret) if kernel else _ssd_products(*args, p=p)
    return y.reshape(b, s, heads, p), state


def _own_maps(bm, cm, c):
    """``C B^T`` of every chunk and group, (B, Z, G, i, j), 0 where j > i."""
    b, s, groups, n = bm.shape
    cut = lambda t: t.reshape(b, s // c, c, groups, n)  # noqa: E731
    own = jnp.einsum("bzign,bzjgn->bzgij", cut(cm), cut(bm), precision=HIGHEST)
    return jnp.where(jnp.tril(jnp.ones((c, c), bool)), own, 0.0)


def _ssd_products(x, dt, cum, bm, cm, *, p):
    """The chunks' products in ``jax.numpy``. ``x`` (B, S, q); ``dt`` (B, S,
    H); ``cum`` (B, Z, C, H); ``bm``, ``cm`` (B, S, G, N) -> (y (B, S, q), the
    last state (B, N, q))."""
    b, s, q = x.shape
    z, c, heads = cum.shape[1:]
    groups, n = bm.shape[2:]
    r, qg = heads // groups, q // groups
    dot = functools.partial(jnp.einsum, precision=HIGHEST)
    over = functools.partial(jnp.repeat, repeats=p, axis=-1)  # a head's number over its lanes
    xd = x.reshape(b, z, c, q) * over(dt.reshape(b, z, c, heads))
    own = jnp.repeat(_own_maps(bm, cm, c), r, axis=2)  # (B, Z, H, i, j)
    bm, cm = bm.reshape(b, z, c, groups, n), cm.reshape(b, z, c, groups, n)

    def by_group(spec, maps, wide):  # a group's (..., N) maps against its lanes of (..., q)
        return jnp.concatenate([dot(spec, maps[..., g, :], wide[..., g * qg:(g + 1) * qg]) for g in range(groups)], axis=-1)

    # within a chunk: a head's L . C B^T against the head's lanes of dt X
    by_head = jnp.swapaxes(cum, 2, 3)  # (B, Z, H, C)
    decays = jnp.exp(jnp.minimum(by_head[..., :, None] - by_head[..., None, :], 0.0))  # L (B, Z, H, i, j); where j > i ``own`` is 0
    y = dot("bzhij,bzjhp->bzihp", own * decays, xd.reshape(b, z, c, heads, p)).reshape(b, z, c, q)
    # between chunks: a chunk reads the state as it meets it and hands on what it adds, both plain products a group
    toward_end = xd * over(jnp.exp(cum[:, :, -1:] - cum))

    def one(state, xs):  # (B, N, q)
        bm, cm, cum, toward_end = xs
        met = by_group("bin,bnq->biq", cm, state) * over(jnp.exp(cum))
        return over(jnp.exp(cum[:, -1]))[:, None] * state + by_group("bjn,bjq->bnq", bm, toward_end), met

    lead = lambda t: jnp.moveaxis(t, 1, 0)  # noqa: E731
    state, met = jax.lax.scan(one, jnp.zeros((b, n, q), jnp.float32), (lead(bm), lead(cm), lead(cum), lead(toward_end)))
    y = y + jnp.moveaxis(met, 0, 1)
    return y.reshape(b, s, q), state


def _ssd_kernel(x_ref, dt_ref, cum_ref, cumt_ref, own_ref, cm_ref, bm_ref, y_ref, s_ref, *, p, width):
    """One chunk of one block of lanes: ``x`` (C, lanes); ``dt``, ``cum`` (C,
    the block's heads) and ``cum`` again as (heads, C); ``C B^T`` (C, C) with
    its upper half 0; ``C`` and ``B`` (C, N). ``s_ref`` (N, lanes) is the
    state: the output block the chunk axis revisits, empty at the first chunk.
    A lane tile of ``width`` at a time; the ``width // p`` heads of a tile of
    narrow heads each take the tile's ``dt X`` under their lanes' mask. ``B``
    comes positions-major as ``x`` and ``C`` do and is turned here, a tile a
    step: handed over as (N, S) it made XLA lay the convolution's whole output
    that way round, since all three are slices of it, and copy ``x`` and ``y``
    (33.5 MB each at Granite's 1,024) into and out of the kernel's layout."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    c, lanes = x_ref.shape
    share = max(1, width // p)
    dot = functools.partial(jnp.dot, precision=HIGHEST, preferred_element_type=jnp.float32)
    dt, cum, cumt, own, cm, bmt = dt_ref[...], cum_ref[...], cumt_ref[...], own_ref[...], cm_ref[...], bm_ref[...].T
    last = cum[c - 1:c, :]
    head_of = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) // p
    for t in range(lanes // width):
        at, first = slice(t * width, (t + 1) * width), t * width // p

        def over(col):  # (rows, heads) -> (rows, width): each of the tile's heads' number over its lanes
            wide = jnp.broadcast_to(col[:, first:first + 1], (col.shape[0], width))
            for k in range(1, share):
                wide = jnp.where(head_of == k, jnp.broadcast_to(col[:, first + k:first + k + 1], wide.shape), wide)
            return wide

        xd, state = x_ref[:, at] * over(dt), s_ref[:, at]
        y = dot(cm, state) * jnp.exp(over(cum))
        for k in range(share):
            h = first + k
            decays = jnp.exp(jnp.minimum(cum[:, h:h + 1] - cumt[h:h + 1, :], 0.0))  # L (i, j); where j > i ``own`` is 0
            y += dot(own * decays, xd if share == 1 else jnp.where(head_of == k, xd, 0.0))
        y_ref[:, at] = y
        s_ref[:, at] = jnp.exp(over(last)) * state + dot(bmt, xd * jnp.exp(over(last - cum)))


@functools.partial(jax.jit, static_argnames=("p", "interpret"))
def _ssd_call(x, dt, cum, bm, cm, *, p, interpret=False):
    """The kernel over (sequence, block of lanes, chunk), the chunks in order.
    Shapes as ``_ssd_products``. Jitted: lowered once a shape, not at each of a
    program's calls."""
    b, s, q = x.shape
    z, c, heads = cum.shape[1:]
    groups, n = bm.shape[2:]
    qg = q // groups
    lanes = math.gcd(qg, _LANE_BLOCK) if qg % _LANES == 0 else qg  # the interpreter cuts lanes anywhere
    width = math.gcd(lanes, max(_LANES, p))  # a lane tile, a wider head whole; fewer lanes only under the interpreter
    if width % p:
        raise ValueError(f"heads of {p} channels do not tile {lanes} lanes")
    per, blocks, in_group = lanes // p, q // lanes, qg // lanes  # heads a block, blocks, blocks a group
    by_block = lambda t: jnp.moveaxis(t.reshape(b, s, blocks, per), 2, 1)  # noqa: E731 - (B, S, H) -> (B, blocks, S, heads a block)
    dt, cum = by_block(dt), by_block(cum.reshape(b, s, heads))
    small = pl.BlockSpec((None, None, c, per), lambda i, d, t: (i, d, t, 0))
    wide = pl.BlockSpec((None, c, lanes), lambda i, d, t: (i, t, d))
    maps = pl.BlockSpec((None, c, n), lambda i, d, t: (i, t, d // in_group))  # a group's columns of (B, S, G x N)
    return pl.pallas_call(
        functools.partial(_ssd_kernel, p=p, width=width),
        out_shape=(jax.ShapeDtypeStruct((b, s, q), jnp.float32), jax.ShapeDtypeStruct((b, n, q), jnp.float32)),
        grid=(b, blocks, z),
        in_specs=[
            wide, small, small,
            pl.BlockSpec((None, None, per, c), lambda i, d, t: (i, d, 0, t)),
            pl.BlockSpec((None, None, None, c, c), lambda i, d, t: (i, t, d // in_group, 0, 0)),
            maps, maps,
        ],
        out_specs=(wide, pl.BlockSpec((None, n, lanes), lambda i, d, t: (i, 0, d))),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"),
                                             vmem_limit_bytes=_VMEM_LIMIT),
        name="ssd_prefill",
        interpret=interpret,
    )(x, dt, cum, jnp.swapaxes(cum, 2, 3), _own_maps(bm, cm, c), cm.reshape(b, s, groups * n), bm.reshape(b, s, groups * n))
