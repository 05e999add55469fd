"""The selective state-space recurrence (Mamba: Gu, Dao, arXiv:2312.00752): a
layer's diagonal state and its three forms here.

A sequence keeps, a layer, a state ``h`` (N x d_in), float32. A token with the
short convolution's output ``c`` (d_in), its step ``Dl`` (d_in, positive),
its input and output maps ``B``, ``C`` (N each) and the layer's ``A`` (N x
d_in, negative):

    h_t = exp(Dl_t A) * h_{t-1} + (Dl_t c_t) B_t^T;    y_t = C_t h_t

(the skip ``D * c_t`` and the gate are the caller's: ``models/phi4flash.py``).

**Mamba-2's state** (Dao, Gu, arXiv:2405.21060; ``models/falcon_h1.py``) is the
same arithmetic over (N, channels) with two things coarser: ``B`` and ``C`` are
shared by a **group** of channels and not by all (``bm``, ``cm`` (B, G, N):
group ``g`` is channels ``g d_in / G ..``), and the decay is one number a head,
``exp(dt_h A_h)``, constant down a column and across the head's channels. The
step and the update take it **given** (``decay`` (B, d_in), in ``a``'s place):
a sequence then costs as many exponentials as it has heads, computed by the
caller, and not one an entry of the state. Between chunks of a prompt that
form is a matrix product: ``ops/ssd.py``.

* ``ssm_step``: that, for B rows, in ``jax.numpy``. A decode step off the TPU
  and the tests' statement of the rule; ``ssm_read`` is its ``y`` from a state
  as stored.
* ``selective_scan_update``: the same arithmetic as a Pallas TPU kernel over the
  engine's state pool: each row's state is read where it lies, once, and written
  back in place, once (``input_output_aliases``); the row's index and the
  layer's are scalars prefetched for the block's index map, as
  ``gated_delta_update``'s.
* ``selective_scan_chunked``: a whole prompt from an empty state. The
  recurrence is elementwise over (N, d_in) and no matrix product carries it, so
  a prompt is walked a token at a time: on a TPU by a kernel whose state stays in
  fast memory while the prompt's ``Dl``, ``c``, ``B`` and ``C`` stream past in
  chunks of ``CHUNK`` positions (a grid step a chunk and a block of channels),
  elsewhere by a scan over the positions. **A padded position passes the state
  through**: its ``Dl`` is 0, so its decay is exp(0) = 1 and its input 0,
  exactly.

**How a state lies in memory.** (N, d_in): the 16 state dimensions in the
sublanes and the channels in the lanes, whole tiles both ways. (d_in, N) as the
published layer writes it would pad 16 lanes to 128. ``A`` lies alike.

**A step may be replayed at its position.** ``advance`` (B,) says whether a row
takes the update; where it is false the state stays as stored and ``y`` is read
from the stored state, which is what the update had left there
(``models/olmo_hybrid.py`` says who needs that).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128  # positions of a prompt one grid step of the prefill's kernel walks
_LANES = 128
_CHANNELS = 512  # channels a grid step of the prefill's kernel holds the state of: 8 vector registers of 16 x 128
_TOKENS = 8  # positions the prefill's kernel reads a load: a float32 tile's sublanes
_VMEM_LIMIT = 48 << 20


def _over_channels(m, d_in):
    """``B`` or ``C`` against a state (B, N, d_in): (B, N) -> (B, N, 1), every
    channel's; (B, G, N) -> (B, N, d_in), group ``g``'s over its ``d_in / G``
    channels. Float32."""
    m = m.astype(jnp.float32)
    if m.ndim == 2:
        return m[:, :, None]
    return jnp.repeat(jnp.swapaxes(m, 1, 2), d_in // m.shape[1], axis=2)


def ssm_read(state, cm):
    """``y = C h``: ``state`` (B, N, d_in) float32, ``cm`` (B, N), or (B, G, N)
    a group of channels -> (B, d_in) float32. Products and a sum, elementwise:
    no matmul whose precision a backend may choose."""
    return jnp.sum(state * _over_channels(cm, state.shape[-1]), axis=1)


def ssm_step(state, c, dl, bm, cm, a, advance=None, *, decay=None):
    """One token a row. ``state`` (B, N, d_in) float32; ``c``, ``dl`` (B,
    d_in); ``bm``, ``cm`` (B, N), or (B, G, N) a group of channels; ``a`` (N,
    d_in), negative, or None with ``decay`` (B, d_in) given in ``exp(dl a)``'s
    place; ``advance`` (B,) bool or None (every row). -> (y (B, d_in) float32,
    the new state). A caller that must get the same ``y`` from an update and
    from its replay reads it with ``ssm_read`` from the state *as stored*
    (``gated_delta.gated_delta_step`` says why)."""
    c, dl = c.astype(jnp.float32), dl.astype(jnp.float32)
    decay = jnp.exp(dl[:, None, :] * a[None]) if decay is None else decay.astype(jnp.float32)[:, None, :]
    new = decay * state + (dl * c)[:, None, :] * _over_channels(bm, state.shape[-1])
    if advance is not None:
        new = jnp.where(advance[:, None, None], new, state)
    return ssm_read(new, cm), new


def can_use_selective_scan_kernel(d_in: int, n: int) -> bool:
    """Platform and static shape alone, as ``can_use_paged_kernel``: a TPU,
    channels of whole lane tiles and state dimensions of whole sublane tiles."""
    return jax.default_backend() == "tpu" and d_in % _LANES == 0 and n % 8 == 0


def _over_lanes(x, width):
    """(..., N) -> (..., N, width) float32: a state dimension's number over a
    tile's lanes, as the kernels multiply it into (N, width) of a state."""
    x = x.astype(jnp.float32)
    return jnp.broadcast_to(x[..., None], (*x.shape, width))


# -- a decode step's update, over the pool ------------------------------------------------


_ROWS = 64  # state dimensions a pass of the update's kernel holds in registers: 8 vector registers a lane tile


def _update_kernel(li_ref, rows_ref, adv_ref, c_ref, dl_ref, b_ref, cm_ref, a_ref, s_ref, y_ref, s_out, *, width, given):
    """One row's state (N, d_in) a grid step, a lane tile and ``_ROWS`` state
    dimensions at a time. ``b_ref``, ``cm_ref`` (1, G, N, width): a tile's
    group is fixed where the kernel is traced. ``given``: ``a_ref`` is the
    row's decays (1, 1, d_in), not the layer's ``A``."""
    del li_ref, rows_ref
    advance = adv_ref[pl.program_id(0)] != 0
    groups, n = b_ref.shape[1:3]
    d_in = s_ref.shape[-1]
    for first in range(0, d_in, width):
        at, g = pl.ds(first, width), first // (d_in // groups)
        dl = dl_ref[0, :, at]
        x, y = dl * c_ref[0, :, at], None
        for lo in range(0, n, _ROWS):
            some = pl.ds(lo, min(_ROWS, n - lo))
            state = s_ref[0, 0, some, at]
            decay = a_ref[0, :, at] if given else jnp.exp(dl * a_ref[0, some, at])
            new = jnp.where(advance, decay * state + x * b_ref[0, g, some, :], state)
            s_out[0, 0, some, at] = new
            part = jnp.sum(new * cm_ref[0, g, some, :], axis=0, keepdims=True)
            y = part if y is None else y + part
        y_ref[0, :, at] = y


def selective_scan_update(pool, layer, rows, advance, c, dl, bm, cm, a=None, *, decay=None, interpret=False):
    """``ssm_step`` over the state pool where it lies. ``pool`` (layers, rows,
    N, d_in) float32; ``layer`` (traced) and ``rows`` (B,) name each sequence's
    state, ``advance`` (B,) bool as above; ``a`` (layers, N, d_in) float32,
    negative, the stack's, or None with ``decay`` (B, d_in) given; the rest as
    ``ssm_step`` takes them. -> (y (B, d_in) float32, the pool, the rows named
    updated in place; every other row untouched). Rows that several sequences
    name (the null row of inactive slots) must not advance."""
    b, d_in = c.shape
    if bm.ndim == 2:
        bm, cm = bm[:, None], cm[:, None]
    groups, n = bm.shape[1:]
    width = min(_LANES, d_in)  # the interpreter cuts lanes anywhere
    if (d_in // groups) % width:
        raise ValueError(f"{groups} groups of {d_in} channels are not whole lane tiles of {width}")
    row = pl.BlockSpec((1, 1, d_in), lambda i, *_: (i, 0, 0))
    col = pl.BlockSpec((1, groups, n, width), lambda i, *_: (i, 0, 0, 0))
    layers = pl.BlockSpec((1, n, d_in), lambda i, li, rows, adv: (li[0], 0, 0))
    state = pl.BlockSpec((1, 1, n, d_in), lambda i, li, rows, adv: (li[0], rows[i], 0, 0))
    given = decay is not None
    y, pool = pl.pallas_call(
        functools.partial(_update_kernel, width=width, given=given),
        out_shape=(jax.ShapeDtypeStruct((b, 1, d_in), jnp.float32), jax.ShapeDtypeStruct(pool.shape, pool.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b,),
            in_specs=[row, row, col, col, row if given else layers, state], out_specs=(row, state),
        ),
        input_output_aliases={8: 1},  # the pool, counted with the three prefetched scalars
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT),
        name="selective_scan_update",
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), rows.astype(jnp.int32), advance.astype(jnp.int32),
        c.astype(jnp.float32)[:, None], dl.astype(jnp.float32)[:, None], _over_lanes(bm, width), _over_lanes(cm, width),
        decay.astype(jnp.float32)[:, None] if given else a, pool,
    )
    return y[:, 0], pool


# -- a prompt, from an empty state ---------------------------------------------------------


def _scan_kernel(c_ref, dl_ref, b_ref, cm_ref, a_ref, y_ref, s_ref, h, *, width, tokens):
    """``CHUNK`` positions of one block of channels: the state ``h`` (N,
    channels) stays in fast memory from a sequence's first chunk to its last;
    ``tokens`` positions a load, each a lane tile at a time."""
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _():
        h[...] = jnp.zeros_like(h)

    chunk, channels = c_ref.shape[1:]
    tiles = [slice(first, first + width) for first in range(0, channels, width)]
    a = [a_ref[:, at] for at in tiles]

    def some(i, hs):
        first = pl.multiple_of(i * tokens, tokens)
        c, dl = c_ref[0, pl.ds(first, tokens), :], dl_ref[0, pl.ds(first, tokens), :]
        x = dl * c
        ys = []
        for j in range(tokens):
            b, cm = b_ref[0, first + j], cm_ref[0, first + j]  # (N, width)
            hs = [jnp.exp(dl[j:j + 1, at] * a_k) * h_k + x[j:j + 1, at] * b for at, a_k, h_k in zip(tiles, a, hs)]
            ys.append(jnp.concatenate([jnp.sum(h_k * cm, axis=0, keepdims=True) for h_k in hs], axis=-1))
        y_ref[0, pl.ds(first, tokens), :] = jnp.concatenate(ys, axis=0)
        return hs

    hs = jax.lax.fori_loop(0, chunk // tokens, some, [h[:, at] for at in tiles])
    for at, h_k in zip(tiles, hs):
        h[:, at] = h_k

    @pl.when(t == pl.num_programs(2) - 1)
    def _():
        s_ref[0] = h[...]


def _scan_call(c, dl, bm, cm, a, *, interpret=False):
    b, s, d_in = c.shape
    n = bm.shape[-1]
    width = min(_LANES, d_in)
    channels, chunk = min(_CHANNELS, d_in), min(CHUNK, s)
    tokens = min(_TOKENS, chunk)
    if d_in % channels or s % chunk or chunk % tokens:
        raise ValueError(f"a prompt of {s} positions by {d_in} channels is not whole chunks of {chunk} by {channels}")
    seq = pl.BlockSpec((1, chunk, channels), lambda i, d, t: (i, t, d))
    col = pl.BlockSpec((1, chunk, n, width), lambda i, d, t: (i, t, 0, 0))
    return pl.pallas_call(
        functools.partial(_scan_kernel, width=width, tokens=tokens),
        out_shape=(jax.ShapeDtypeStruct((b, s, d_in), jnp.float32), jax.ShapeDtypeStruct((b, n, d_in), jnp.float32)),
        grid=(b, d_in // channels, s // chunk),
        in_specs=[seq, seq, col, col, pl.BlockSpec((n, channels), lambda i, d, t: (0, d))],
        out_specs=(seq, pl.BlockSpec((1, n, channels), lambda i, d, t: (i, 0, d))),
        scratch_shapes=[pltpu.VMEM((n, channels), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"),
                                             vmem_limit_bytes=_VMEM_LIMIT),
        name="selective_scan_prefill",
        interpret=interpret,
    )(c.astype(jnp.float32), dl.astype(jnp.float32), _over_lanes(bm, width), _over_lanes(cm, width), a)


def selective_scan_chunked(c, dl, bm, cm, a, *, kernel=None, interpret=False):
    """A prompt from an empty state. ``c``, ``dl`` (B, S, d_in) with ``dl`` 0
    at every padded position; ``bm``, ``cm`` (B, S, N); ``a`` (N, d_in)
    float32, negative. -> (y (B, S, d_in) float32, the state after the last
    position (B, N, d_in) float32). On a TPU the kernel above (S whole chunks
    of ``CHUNK``, or fewer positions than one); elsewhere a scan over the
    positions, the same arithmetic a token at a time."""
    b, s, d_in = c.shape
    n = bm.shape[-1]
    if can_use_selective_scan_kernel(d_in, n) if kernel is None else kernel:
        return _scan_call(c, dl, bm, cm, a, interpret=interpret)

    def token(state, xs):
        y, state = ssm_step(state, *xs, a)
        return state, y

    state, y = jax.lax.scan(token, jnp.zeros((b, n, d_in), jnp.float32),
                            tuple(jnp.swapaxes(x, 0, 1) for x in (c, dl, bm, cm)))
    return jnp.swapaxes(y, 0, 1), state
