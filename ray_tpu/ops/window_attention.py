"""Attention in the three forms a model of window and full layers asks for, none
of which ``ops/attention.py`` has (its flash path takes plain causal masks from
position 0): a banded prefill, one query position over rows under a mask, and a
decode step over a ring of ``window`` rows a sequence. Each in two scorings,
told apart by ``lam``: **differential** (Ye et al., arXiv:2410.05258;
Phi-4-mini-flash) where ``lam`` is a number, **plain** (one softmax of grouped
queries: K-EXAONE's window layers) where it is ``None``. The plain form has
``H`` query heads of ``d`` over ``G`` K/V heads of ``d``, head ``h`` on K/V head
``h // (H / G)``, and nothing below about pairs holds for it but the layout:
what is called a pair is then a head.

Heads come in **pairs**. A query pair is 128 values ``[q1; q2]``, a key pair
``[k1; k2]`` and a value head ``[v1; v2]``, 64 + 64 each, two query pairs a
K/V pair (``(..., pairs, 128)``). With the layer's number ``lam``:

    o = (softmax(q1 K1^T x scale) - lam softmax(q2 K2^T x scale)) V

over the positions the mask lets through; ``scale`` is 64^-1/2 however the
pair is packed. What follows (a norm over ``o``'s 128 values, a constant
factor, ``W_o``) is the model's.

**One trick serves both kernels.** A key pair is stored as one head of 128
and read whole; the queries ``[q1; 0]`` and ``[0; q2]`` score it against k1
and against k2 alone (``split_queries``). So the paged kernel the tree has
(``paged_decode_attention``) runs the full and the cross layers over the
shared cache with twice the query heads and the subtraction outside; and the
ring kernel here does the same inside.

* ``diff_attention_prefill``: a whole prompt from position 0, in query blocks
  against the keys a block can see: the block itself and the one before it
  under a window, every earlier block without one. No (S, S) score tensor.
* ``diff_attention_rows``: one query position a sequence over rows handed
  over with a mask: the decode step's gather path (the CPU backend) and the
  tests' statement of what the two kernels compute.
* ``ring_window_attention``: a decode step over a **ring** of ``window`` rows
  a sequence, contiguous on the device ((layers, state rows, window x K/V
  pairs, 128), in the pool's state row): a Pallas TPU kernel that takes a
  sequence's whole ring as one block (the block's index is the layer and the
  state row, prefetched scalars; the pipeline copies the next sequence's ring
  while this one is scored), does both softmaxes over the ``live`` rows and
  the subtraction (or the one softmax), and writes ``o``. A row's place in
  the ring says nothing: position ``p`` lies at ``p % window`` and the mask is
  ``row < min(p + 1, window)``. **The kernel writes the step's own row too**:
  the ring comes in with the row at ``p % window`` stale, the kernel puts the
  new K and V there in the block it holds, scores that block, and copies the
  sublane tiles that cover the row back into the ring in HBM, which is the
  kernel's output in place of its input. **A key that carries a rotary is
  rotated at its absolute position before it is handed over**: a score then
  depends on ``t - s`` wherever row ``s % window`` lies, the mask by count stays
  right, and the ring's order is as free as without a rotary. A scatter ahead of the kernel did the
  same for ~1.4 us an index on the chip, 48 sequences x K and V a layer: more
  than the attention took. An inactive slot's ring is the null row, which
  every inactive slot shares: it is read behind a mask of nothing and never
  written.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.paged_attention import tile_rows

_NEG_INF = -1e30
_VMEM_LIMIT = 48 << 20  # two buffers each of a ring's K and V (1.3 MB at the published widths) and a (48, 5120) score block


def split_queries(qp):
    """``qp`` (..., pairs, 2 x d) -> (``[q1; 0]``, ``[0; q2]``), each as
    wide as a stored key pair."""
    first = jnp.arange(qp.shape[-1]) < qp.shape[-1] // 2
    return jnp.where(first, qp, 0), jnp.where(first, 0, qp)


def _two_softmaxes(qp, k, mask, lam, scale):
    """``qp`` (B, Q, G, R, 2d), ``k`` (B, M, G, 2d), ``mask`` broadcast to (B,
    G, R, Q, M) -> softmax1 - lam softmax2, float32."""
    d = k.shape[-1] // 2
    dot = functools.partial(jnp.einsum, "bqgrd,bkgd->bgrqk", preferred_element_type=jnp.float32)
    s1 = jnp.where(mask, dot(qp[..., :d], k[..., :d]) * scale, _NEG_INF)
    s2 = jnp.where(mask, dot(qp[..., d:], k[..., d:]) * scale, _NEG_INF)
    return jax.nn.softmax(s1, axis=-1) - lam * jax.nn.softmax(s2, axis=-1)


def _weights(q, k, mask, lam, scale):
    """The attention weights of either scoring: ``q`` (B, Q, G, R, w), ``k``
    (B, M, G, w), ``mask`` broadcast to (B, G, R, Q, M) -> float32."""
    if lam is not None:
        return _two_softmaxes(q, k, mask, lam, scale)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", q, k, preferred_element_type=jnp.float32) * scale
    return jax.nn.softmax(jnp.where(mask, s, _NEG_INF), axis=-1)


def window_attention_rows(qp, k, v, live, lam=None, *, scale):
    """One query position a sequence: ``qp`` (B, pairs, 2d), ``k``, ``v`` (B,
    M, K/V pairs, 2d), ``live`` (B, M) bool the rows that count (a sequence
    with none gets zeros) -> ``o`` (B, pairs, 2d) float32. ``lam`` None: the
    plain form, (B, heads, d) over (B, M, K/V heads, d)."""
    b, pairs, wide = qp.shape
    groups = k.shape[2]
    q = qp.reshape(b, 1, groups, pairs // groups, wide)
    p = _weights(q, k, live[:, None, None, None, :], lam, scale)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    return jnp.where(jnp.any(live, axis=1)[:, None, None], o.reshape(b, pairs, wide), 0.0)


def window_attention_prefill(qp, k, v, lam=None, *, scale, window=None, block: int = 512):
    """A prompt from position 0: ``qp`` (B, S, pairs, 2d), ``k``, ``v`` (B, S,
    K/V pairs, 2d) -> ``o`` (B, S, pairs, 2d) float32. Position t sees
    positions ``t - window + 1 .. t`` (from 0 without a window). Query blocks
    of ``window`` rows (``block`` without one) against, under a window, their
    own rows and the block's before them, else every row: the masks are exact,
    the blocks only bound what is scored. ``lam`` None: the plain form."""
    b, s, pairs, wide = qp.shape
    groups = k.shape[2]
    rows = min(window or block, s)
    if s % rows:
        raise ValueError(f"a prompt of {s} positions is not whole blocks of {rows}")
    q = qp.reshape(b, s, groups, pairs // groups, wide)
    if window is not None:  # the block before the first is padding behind the mask
        k, v = (jnp.pad(x, ((0, 0), (rows, 0), (0, 0), (0, 0))) for x in (k, v))
    span = 2 * rows if window is not None else s

    def one(i):
        q_pos = i * rows + jnp.arange(rows)
        first = (i - 1) * rows if window is not None else 0  # the position of the first key scored
        k_pos = first + jnp.arange(span)
        sees = (k_pos[None, :] <= q_pos[:, None]) & (k_pos[None, :] >= 0)
        if window is not None:
            sees &= k_pos[None, :] > q_pos[:, None] - window
        at = i * rows if window is not None else 0  # in the padded rows, where that key lies
        ks, vs = (jax.lax.dynamic_slice_in_dim(x, at, span, axis=1) for x in (k, v))
        p = _weights(jax.lax.dynamic_slice_in_dim(q, i * rows, rows, axis=1), ks, sees, lam, scale)
        return jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(vs.dtype), vs, preferred_element_type=jnp.float32)

    o = jax.lax.map(one, jnp.arange(s // rows))  # (blocks, B, rows, G, R, 2d)
    return jnp.moveaxis(o, 0, 1).reshape(b, s, pairs, wide)


# the names the differential model's calls have always used: the same functions, ``lam`` a number
diff_attention_rows, diff_attention_prefill = window_attention_rows, window_attention_prefill


# -- the ring, a decode step ----------------------------------------------------------------


def can_use_ring_kernel(window: int, kv_pairs: int, wide: int, dtype) -> bool:
    """Platform and static shape alone, as ``can_use_paged_kernel``: a TPU, a
    stored pair of whole lane tiles and a ring of whole sublane tiles."""
    return jax.default_backend() == "tpu" and wide % 128 == 0 and (window * kv_pairs) % tile_rows(dtype) == 0


def _ring_kernel(li_ref, rows_ref, live_ref, at_ref, q_ref, *refs, half, scale, kv_pairs, span):
    """One sequence's ring a grid step. ``q_ref`` (1, 2 x half, 2d): the
    ``[q1; 0]`` queries, padded to ``half`` rows, then the ``[0; q2]``; in the
    plain form (``half`` 0) (1, heads, d), the queries as they are, and no
    ``lam_ref`` among ``refs``, which are otherwise ``lam_ref, own_ref, row_ref,
    nk_ref, nv_ref, k_ref, v_ref, o_ref, k_out, v_out, sem``.
    ``own_ref`` (2 x half, columns): 0 where a column (a row of the ring and a
    K/V pair, as stored) is the query's own pair's, else ``_NEG_INF``.
    ``nk_ref``, ``nv_ref`` (B, K/V pairs, 2d): every sequence's new row.
    ``k_ref``, ``v_ref``: the ring's block in VMEM, the row at ``at_ref[i]``
    stale; ``k_out``, ``v_out``: the rings in HBM, the same buffers as the
    inputs; ``sem``: a DMA semaphore each.

    The new row is put in its place in the VMEM block (the ``span`` rows of
    whole sublane tiles that cover it: read, patched row by row, stored), those
    tiles start on their way back to the ring in HBM, the block is scored as it
    now lies, and the copies are waited for before the step ends: the next step
    but one's ring lands in this buffer. An inactive slot patches and writes
    nothing: the null row is every inactive slot's."""
    lam_ref = refs[0] if half else None
    own_ref, row_ref, nk_ref, nv_ref, k_ref, v_ref, o_ref, k_out, v_out, sem = refs[bool(half):]
    i = pl.program_id(0)
    live, (cols, wide), sublanes = live_ref[i], k_ref.shape[2:], tile_rows(k_ref.dtype)
    first = at_ref[i] * kv_pairs  # the new row's pairs are rows first .. first + kv_pairs - 1
    start = pl.multiple_of(jnp.minimum(first // sublanes * sublanes, cols - span), sublanes)
    tiles = pl.ds(start, span)
    back = [pltpu.make_async_copy(ring.at[0, 0, tiles], out.at[li_ref[0], rows_ref[i], tiles], sem.at[j])
            for j, (ring, out) in enumerate(((k_ref, k_out), (v_ref, v_out)))]

    @pl.when(live > 0)
    def _():
        place = jax.lax.broadcasted_iota(jnp.int32, (span, wide), 0) - (first - start)
        for new_ref, ring in ((nk_ref, k_ref), (nv_ref, v_ref)):
            # through float32, which holds every value of the ring's type: a select of packed rows is not every chip's
            new, rows = new_ref[i].astype(jnp.float32), ring[0, 0, tiles, :].astype(jnp.float32)
            for j in range(kv_pairs):
                rows = jnp.where(place == j, new[j:j + 1], rows)
            ring[0, 0, tiles, :] = rows.astype(ring.dtype)
        for copy in back:
            copy.start()

    k, v = k_ref[0, 0], v_ref[0, 0]  # (window x K/V pairs, 2d)
    s = jax.lax.dot_general(q_ref[0], k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
    s = jnp.where(row_ref[...] < live, s + own_ref[...], _NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.where(s > 0.5 * _NEG_INF, jnp.exp(s - m), 0.0)
    # a query with no live column of its own (an inactive slot, a padding row) has summed nothing: 0, not 0/0
    acc = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32) / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    o_ref[0] = acc[:half] - lam_ref[...] * acc[half:] if half else acc

    @pl.when(live > 0)
    def _():
        for copy in back:
            copy.wait()


def ring_window_attention(qp, new_k, new_v, ring_k, ring_v, layer, rows, live, at, lam, *, kv_pairs: int, scale,
                          interpret=False):
    """A decode step's write and read of layer ``layer`` (traced) of the rings
    (layers, state rows, window x ``kv_pairs``, 2d), sequence ``b``'s at state
    row ``rows[b]``. ``qp`` (B, pairs, 2d) the queries; ``new_k``, ``new_v``
    (B, ``kv_pairs``, 2d) the position's own K and V, cast to the rings' type;
    ``at`` (B,) the ring row they go to (``position % window``), stale as the
    rings come in; ``live`` (B,) how many of the ring's rows count with the new
    one among them, the first ``live`` (``at < live``; 0: an inactive slot,
    whose output is 0 and whose state row, the null row, is not written);
    ``lam`` a float32 scalar, or None (static): the **plain** form, ``qp`` (B,
    heads, d) scored whole against its own K/V head's columns under one
    softmax, with no split, no second half and no ``lam`` operand. Live
    sequences hold distinct state rows.

    -> (``o`` (B, pairs, 2d) float32, ``ring_k``, ``ring_v``). The rings come
    back in place (``input_output_aliases``), bit for bit what a scatter of the
    new rows leaves: only the whole sublane tiles that cover a new row are
    written, from the block the kernel scored. ``o`` is what
    ``window_attention_rows`` gives over the rings that come back: scores and
    softmaxes in float32, the weights in the rings' type into the weighted
    sums. A call again at the same position writes the same row."""
    b, pairs, wide = qp.shape
    cols = ring_k.shape[2]
    paired = lam is not None
    half = -(-pairs // 8) * 8  # whole float32 sublane tiles: the output's two halves part on a tile
    sublanes = tile_rows(ring_k.dtype)
    span = min(cols, (-(-(kv_pairs - 1) // sublanes) + 1) * sublanes)  # the whole tiles a row's pairs can lie across
    pad = lambda x: jnp.pad(x, ((0, 0), (0, half - pairs), (0, 0)))  # noqa: E731
    q = jnp.concatenate([pad(x) for x in split_queries(qp)], axis=1) if paired else pad(qp)
    q_rows = q.shape[1]
    head, col = jnp.arange(q_rows) % half, jnp.arange(cols)
    own = (col[None, :] % kv_pairs == (head // (pairs // kv_pairs))[:, None]) & (head < pairs)[:, None]
    whole = lambda shape: pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))  # noqa: E731 - the same block every step
    ring = pl.BlockSpec((1, 1, cols, wide), lambda i, li, rows, live, at: (li[0], rows[i], 0, 0))
    in_place = pl.BlockSpec(memory_space=pl.ANY)
    scalars = (jnp.asarray(layer, jnp.int32).reshape(1), rows.astype(jnp.int32), live.astype(jnp.int32), at.astype(jnp.int32))
    # (operand, its block); the differential form alone has ``lam``, second: its calls lower as they always have
    operands = [
        (q, pl.BlockSpec((1, q_rows, wide), lambda i, *_: (i, 0, 0))),
        *([(jnp.full((1, wide), lam, jnp.float32), whole((1, wide)))] if paired else []),
        (jnp.where(own, 0.0, _NEG_INF).astype(jnp.float32), whole((q_rows, cols))),
        ((col // kv_pairs).astype(jnp.int32)[None, :], whole((1, cols))),
        (new_k.astype(ring_k.dtype), whole(new_k.shape)), (new_v.astype(ring_v.dtype), whole(new_v.shape)),
        (ring_k, ring), (ring_v, ring),
    ]
    n_prefetch = len(scalars)
    o, ring_k, ring_v = pl.pallas_call(
        functools.partial(_ring_kernel, half=half if paired else 0, scale=scale, kv_pairs=kv_pairs, span=span),
        out_shape=(jax.ShapeDtypeStruct((b, half, wide), jnp.float32),
                   jax.ShapeDtypeStruct(ring_k.shape, ring_k.dtype), jax.ShapeDtypeStruct(ring_v.shape, ring_v.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch, grid=(b,),
            in_specs=[spec for _, spec in operands],
            out_specs=(pl.BlockSpec((1, half, wide), lambda i, *_: (i, 0, 0)), in_place, in_place),
            scratch_shapes=[pltpu.SemaphoreType.DMA((2,))],
        ),
        # the rings, the last two operands, counted with the prefetched scalars
        input_output_aliases={n_prefetch + len(operands) - 2: 1, n_prefetch + len(operands) - 1: 2},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT),
        name="ring_window_attention",
        interpret=interpret,
    )(*scalars, *(x for x, _ in operands))
    return o[:, :pairs], ring_k, ring_v


def write_spans(arr, lead, starts, updates):
    """``arr[*lead_b, starts[b] : starts[b] + n] = updates[b]`` for every
    ``b``: ``arr`` (*leading, rows, wide), ``lead`` the leading indices (each
    a scalar or (B,)), ``updates`` (B, n, wide). One scatter of B windows, not
    of B x n rows: how a ring's or a flat pool's rows are written where no
    kernel writes them."""
    b = updates.shape[0]
    index = jnp.stack([jnp.broadcast_to(jnp.asarray(i, jnp.int32), (b,)) for i in (*lead, starts)], axis=-1)
    dims = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1, 2), inserted_window_dims=tuple(range(len(lead))),
        scatter_dims_to_operand_dims=tuple(range(len(lead) + 1)))
    return jax.lax.scatter(arr, index, updates.astype(arr.dtype), dims)
