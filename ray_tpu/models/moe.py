"""The expert layer, told which experts it holds. Six kinds call it: LongCat
(12 of 768 outputs chosen, 16 of 512 experts held), Kimi-K2 (8 of 384, 12
held), K-EXAONE (8 of 128, 16 held), LFM2 (4 of 64, all held), Granite 4.0-H
(10 of 72, 36 held), all with gated experts of three matrices over the residual
width, and Nemotron-H (22 of 512, 128 held) with ungated experts of two
matrices that read and write a latent a quarter of that width.

A router over ``n_routed + n_zero`` outputs chooses ``top_k`` of them for
every token; the first ``n_routed`` are experts (SwiGLU, ``e_down (silu(e_gate
v) * e_up v)``, or where the caller hands in no ``e_gate`` the ungated
``e_down relu(e_up v)^2``), the rest are
zero-compute (identity) experts that add ``w * v`` with no matmul (a model
may have none: ``n_zero`` is what the router has beyond ``n_routed``). ``v`` is
what the router reads, ``u``, unless the caller hands the experts' rows apart
(``expert_layer(rows=)``: experts in a latent; the projections into it and out
of it are the caller's dense matmuls, and the result is latent-wide). Three
routing rules stand side by side and the layer's caller names one: ``route``
(LongCat-Flash: a softmax over every output, weights not renormalised),
``route_sigmoid`` (DeepSeek-V3's and Kimi-K2's ``noaux_tc``: sigmoid scores,
weights renormalised over the chosen) and ``route_topk_softmax`` (Granite 4.0:
the largest logits chosen, a softmax over the chosen logits alone); in all a
bias moves the choice and never the weights. A shared expert is no part of this layer: it is a dense
MLP that the kind's own layer adds for every token. A chip
holds experts ``[expert_offset, expert_offset + held)`` of a layer that is
shared over several chips: it routes over all the outputs, computes its own
experts' part of the result for the tokens that chose them and every identity
expert's part (those have no weights, so each chip adds them for its own
tokens), and adds nothing for a choice of an expert that lives elsewhere. On
one chip the layer runs without its exchange.

No capacity and no ``(tokens, experts, capacity)`` tensor: the (token, choice)
rows a held expert owns are sorted by expert and go through a grouped matmul
over the experts held (``grouped_matmul``: ``jax.lax.ragged_dot``, on a TPU
the Pallas kernel of ``ops/grouped_matmul.py`` at a stated tiling) a window at
a time, so no token is ever dropped, whatever the routing. A window is
``window_rows`` compacted rows, a size that follows the rows the held experts expect and not the batch
(a chip that holds 12 of 384 experts owns a thirty-second of a step's rows);
windows are walked on the device until the held rows run out: one for an even
router, none where no held expert was chosen, and a layer that holds every
expert has one window of every row. Inside a window the kernel multiplies a
touched expert by row tiles of at most ``ROW_TILE`` rows (``_row_tile``): a
decode step's window of up to 256 rows is one tile, under which every touched
expert's weights are streamed once; a prefill's window of 512 rows is two, so
that an expert is multiplied by the tile its rows lie in and not by the whole
window; and where a call sends an expert fewer than ``_SPARSE_ROWS`` rows
(Granite's and Nemotron's decode steps, whose windows are 512 rows; Nemotron's
and LFM2's small buckets) the tiles are half that, so that a tile's products
hide under the expert's bytes. The results stay in
the sorted order the grouped matmul wrote them in, and a token sums the rows
at its choices' ranks in the sort (``combine``): no buffer of every (token,
choice) place is zeroed, scattered into, laid anew and read back (PERF.md
section 6, PR 58).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from ray_tpu.ops.grouped_matmul import VMEM_BUDGET, gmm as _gmm, unwritten, vmem_bytes
from ray_tpu.ops.layers import relu2, swiglu

# what ``expert_layer`` counts of its routing, in this order: (token, choice)
# rows sent to held, identity and absent experts; held experts with at least one
# row; the rows of the held expert that got the most (over ``held / touched``
# it says how uneven the choice leaves the load: 1 = even); windows of held
# rows walked (over the calls: 1 = no call spilled past its first window); (row
# tile, expert) pairs a grouped call visits, each an expert's weights streamed
# once (over ``touched``: 1 = no touched expert's rows straddled two row tiles)
COUNTS = ("held", "zero", "absent", "touched", "peak", "windows", "pairs")

# A window holds this many times the (token, choice) rows a call's held experts
# expect of an even router, rounded up to a power of two, and no fewer than
# ``WINDOW_MIN`` (a row tile the grouped matmul streams an expert's weights
# under; PERF.md section 6, PR 38)
WINDOW_MULTIPLE = 2
WINDOW_MIN = 32


# The seeded router: logits of deviation ``ROUTER_SCALE``, so that a token's
# chosen weights span two orders of magnitude with the last choice the
# smallest, as a trained router's do (a flat softmax gives every chosen expert
# ``scale / n_outputs``, and an expert's part then vanishes beside the dense
# paths); the choice bias ``BIAS_SCALE`` x normal, small beside a chosen ``p``.
ROUTER_SCALE = 4.0
BIAS_SCALE = 1e-5


def init_expert_params(key, d_model: int, d_ff: int, held: int, n_outputs: int, dtype=jnp.float32) -> Dict[str, jax.Array]:
    """Seeded weights of one layer: 1/sqrt(fan-in) scales, the router's
    columns times ``ROUTER_SCALE``, the choice bias ``BIAS_SCALE`` x normal.
    ``e_down`` is not scaled down with depth as a dense path's output
    projection is: what an expert writes is weighted by ``scale * p`` first."""
    kr, kb, kg, ku, kd = jax.random.split(key, 5)
    s_in, s_ff = d_model ** -0.5, d_ff ** -0.5
    return {
        "router": (jax.random.normal(kr, (d_model, n_outputs)) * s_in * ROUTER_SCALE).astype(dtype),
        "router_bias": jax.random.normal(kb, (n_outputs,)) * BIAS_SCALE,
        "e_gate": (jax.random.normal(kg, (held, d_model, d_ff)) * s_in).astype(dtype),
        "e_up": (jax.random.normal(ku, (held, d_model, d_ff)) * s_in).astype(dtype),
        "e_down": (jax.random.normal(kd, (held, d_ff, d_model)) * s_ff).astype(dtype),
    }


_copy = jax.jit(lambda x: x + 0)


def routing_counts(pool: Dict):
    """A copy of a pool's ``moe_counts`` (``COUNTS`` summed on the device by
    the decode steps' expert layers) that outlives the pool's donation to the
    next step: enqueued behind whatever writes the pool now, so reading it
    later waits for nothing that step would not have finished anyway."""
    return _copy(pool["moe_counts"])


def expert_param_logical_axes() -> Dict[str, Tuple]:
    return {
        "router": ("embed", None),
        "router_bias": (None,),
        "e_gate": ("expert", "embed", "mlp"),
        "e_up": ("expert", "embed", "mlp"),
        "e_down": ("expert", "mlp", "embed"),
    }


def route(u: jax.Array, router: jax.Array, bias: jax.Array, *, top_k: int, scale: float):
    """``u`` (T, D) -> (weights (T, K) float32, experts (T, K) int32).
    Softmax in float32 over every output; the ``top_k`` of ``p + bias`` are
    chosen (the bias moves the choice only); a chosen output's weight is
    ``scale * p``, not renormalised over the chosen."""
    z = jnp.dot(u.astype(jnp.float32), router.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.softmax(z, axis=-1)
    _, experts = jax.lax.top_k(p + bias.astype(jnp.float32), top_k)
    return scale * jnp.take_along_axis(p, experts, axis=-1), experts


def route_sigmoid(u: jax.Array, router: jax.Array, bias: jax.Array, *, top_k: int, scale: float, eps: float = 1e-20):
    """The second rule, same signature. Sigmoid scores in float32 over every
    output; the ``top_k`` of ``s + bias`` are chosen (one group of experts:
    the published rule first keeps the ``topk_group`` best of ``n_group``
    groups, which at 1 of 1 keeps all; the kind refuses other values); a
    chosen output's weight is ``scale * s / (sum of the chosen s + eps)``
    (``eps`` is the model's: DeepSeek-V3's 1e-20, LFM2's 1e-6)."""
    z = jnp.dot(u.astype(jnp.float32), router.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(z)
    _, experts = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(s, experts, axis=-1)
    return scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + eps), experts


def route_topk_softmax(u: jax.Array, router: jax.Array, bias: Optional[jax.Array], *, top_k: int, scale: float):
    """The third rule, same signature. The ``top_k`` largest router logits are
    chosen (under ``bias`` where the model has one; Granite 4.0 has none and
    hands None) and a chosen output's weight is ``scale`` times the softmax
    over the chosen logits alone, in float32: the softmax over every output
    renormalised over the chosen."""
    z = jnp.dot(u.astype(jnp.float32), router.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    _, experts = jax.lax.top_k(z if bias is None else z + bias.astype(jnp.float32), top_k)
    return scale * jax.nn.softmax(jnp.take_along_axis(z, experts, axis=-1), axis=-1), experts


def window_rows(n_rows: int, held: int, n_outputs: int) -> int:
    """Rows of one window of the grouped matmuls, from static shapes alone:
    the smallest power of two at or above ``WINDOW_MULTIPLE`` times the held
    rows an even router sends (``n_rows x held / n_outputs``), at least
    ``WINDOW_MIN`` and at most every row; and, where it is not every row, at
    most ``_KERNEL_ROWS``: a chip that holds an eighth of the experts owns an
    eighth of a long prefill's rows and walks them 512 at a time (PERF.md
    section 6, PR 43). A layer that holds every expert has one window of every
    row, a prefill's 4,096 too: one call under ``ROW_TILE`` tiles visits the
    same (tile, expert) pairs as eight windows would and sorts and gathers
    once (PERF.md section 6, PR 50). **Every row, past the ridge and no
    whole tiles** (Granite's decode step: 48 tokens x 10 choices = 480, half of
    them absent experts' and sorted to the back), is rounded up to whole tiles
    of ``ROW_TILE``: as one tile of 480 every touched expert was multiplied by
    480 rows, twice its bytes' time at the MXU's peak; in whole tiles the ~240
    held rows fill the first ones and the last is visited by no expert
    (PERF.md section 6, PR 55; which tiles: ``_row_tile``)."""
    window = WINDOW_MIN
    while window * n_outputs < WINDOW_MULTIPLE * n_rows * held:
        window *= 2
    if window < n_rows:
        return min(window, _KERNEL_ROWS)
    return n_rows if n_rows < ROW_TILE else -(-n_rows // ROW_TILE) * ROW_TILE


# The grouped kernel's tiles. A window is walked once, under row tiles of at
# most ``ROW_TILE`` rows: the kernel visits each (row tile, expert) pair that
# holds a row, streams the expert's weights for it and multiplies them by the
# whole tile, so a pair costs the larger of the expert's bytes and the tile's
# products, and a call visits about ``touched + held rows / tile`` pairs. 256 is
# the chip's ridge (197e12 FLOP/s over 819e9 B/s = 240 rows of bfloat16): under
# it a pair is bound by the bytes, and one tile a window streams every touched
# expert once (a decode step's windows, 32-256 rows); over it by the products,
# and a prefill's 512 rows as one tile multiplied every touched expert by rows
# other experts own, at the MXU's peak: 0.21 ms a pair of an expert of 75 MB,
# where tiles of 256 take 0.12 and tiles of 128 save nothing more, their pairs
# being more (PERF.md section 6, PR 49). A power of two, so a window is whole
# tiles; rows that are no whole tiles (144) stay one. A row's result does not
# depend on its tile: the contraction's order is the weight tile's.
# **At the ridge the products do not hide**: the copies run at 90-92% of the
# chip's bandwidth, so a tile of 256 rows' products (8.2 us for 6.3 MB) stand
# beside bytes that take 8.4, and a pair costs 1.10-1.11 times a pair under 128
# rows inside a decode program (1.04-1.05 for a matrix in one weight tile
# alone; where the contraction is cut, the tile of rows is copied again with
# every weight tile, 0.46 MB at 256 rows of Nemotron's ``e_down``). That is
# worth paying where an expert owns much of the tile, and a loss where a tile
# holds many experts of a few rows each: ``_row_tile`` halves the tile where a
# call sends an expert fewer than ``_SPARSE_ROWS`` rows in the mean (the call's
# (token, choice) rows over the router's outputs, which ``expert_layer`` knows
# and the operands of one grouped call do not say: Nemotron's decode step and
# its 256 bucket hand the kernel the same (512, 1,024) rows). Set by the
# programs alone on the chip, ms a run at 256 -> 128 (PERF.md section 6, PR 60):
# decode steps, 2.06 rows an expert 15.63 -> 14.89 (Nemotron) and 6.7 rows 21.04
# -> 20.41 (Granite; tiles of 64 20.76: more pairs, more grid steps); prefill
# buckets, 11 rows 16.52 -> 15.73 and 22 rows 23.17 -> 22.60 (Nemotron), 16 rows
# 12.86 -> 12.60 (LFM2); and a tie within a percent or a loss at 32 rows 14.94
# -> 14.79 (LFM2), 36 rows 19.34 -> 19.43 (Granite), 44 rows 34.79 -> 34.70
# (Nemotron), which keep 256 as every larger bucket does. A straddling expert's
# second pair costs no second copy where its matrix is one weight tile (the
# block index does not change), which is why more pairs cost less than the
# arithmetic has it. Only for a matrix that fits a weight tile: the three older
# kinds' go by in 2 MB tiles under a smaller row tile (below), 5-11% dearer in
# their prefills, and keep ``ROW_TILE``.
# An expert's matrix goes by in tiles of at most ``_WEIGHT_TILE`` elements (8 MB
# of bfloat16) where the products bound the call or the matrix fits one whole,
# and of a quarter of that where the copies bound it; the contraction whole
# where it is no longer than ``_WHOLE_K``, each tile as wide as it can be
# (PERF.md section 6, PR 51; one call alone on the chip, us a call, tiles of 2
# MB -> 8). Under a row tile of 256 a grid step's fixed cost (the accumulator
# zeroed, the masked store: ~0.26 us) hides under no copy, and a quarter of the
# steps takes 5-11% off: 680 -> 645 and 725 -> 642 (LFM2), 690 -> 650 and 679
# -> 638 (K-EXAONE), 567 -> 527 and 558 -> 522 (Kimi-K2), 649 -> 616 and 648
# -> 603 (LongCat); 12.6 MB reads what 8 does. A decode step's one row tile is
# bound by the copies, which run at 90-92% of 819 GB/s at any tile (the copies
# alone, with no products under them, read the same, and so do two and four in
# flight); there a larger tile only lengthens the first copy, under which
# nothing runs: 538 -> 543 and 534 -> 540 at 128 rows alone, 10.73 -> 10.86 ms
# for the 21 calls of K-EXAONE's step, so those keep 2 MB. What a decode call
# does pay for is a tile of short runs at a stride of a power of two: LFM2's
# ``e_down`` in tiles of 1,536 x 512 of 2,048 columns (runs of 1 KB, 4 KB
# apart) took 610 us for 476 us of bytes, and whole, in one run, takes 566, as
# ``e_gate`` does at any tile.
ROW_TILE = 256
_WEIGHT_TILE = 4 << 20
_WHOLE_K = 2048
_KERNEL_ROWS = 512  # the most rows of one row tile, and of a window that is not every row
_SPARSE_ROWS = 24  # rows a call sends an expert in the mean under which a row tile is half of ``ROW_TILE``


def _row_tile(rows: int, an_expert: float = ROW_TILE, weights: int = 0) -> int:
    """Rows of a row tile of a window of ``rows`` rows, from static shapes
    alone: ``an_expert``, the rows the call sends an expert in the mean, and
    ``weights``, the elements of an expert's matrix (a caller that gives
    neither gets ``ROW_TILE``). Rows that are no whole tiles of ``ROW_TILE``
    are one tile; whole tiles are ``ROW_TILE`` rows where an expert owns much
    of one, half that where a tile holds many experts of a few rows each and
    the matrix fits a weight tile (the comment above has the measurements)."""
    if rows % ROW_TILE:
        return rows
    return ROW_TILE // 2 if an_expert < _SPARSE_ROWS and weights <= _WEIGHT_TILE else ROW_TILE


def _widest(size: int, most: int) -> int:
    """The widest whole lanes (128) at or under ``most`` that divide ``size``
    (``size`` itself where it is whole lanes and no more), 0 where none do."""
    return next((w for w in range(min(size, most) // 128 * 128, 0, -128) if size % w == 0), 0)


def _weight_tile(k: int, n: int, row_tile: int) -> Tuple[int, int]:
    """(rows, columns) of a matrix (k, n) a tile under a row tile of
    ``row_tile`` rows. ``_WEIGHT_TILE`` elements at the most where the products
    bound the call (a row tile at the ridge) or the matrix fits a tile whole
    (1,536 x 2,048 and 2,048 x 1,536 go by in one run), a quarter of that where
    the copies bound it; the contraction whole up to ``_WHOLE_K`` and else its
    widest divisor of whole lanes that leaves a tile ``_WHOLE_K`` columns
    (7,168: 1,792 of 8 MB, 512 of 2), then the widest whole lanes that divide
    ``n``. 0 for a side that no whole lanes divide."""
    most = _WEIGHT_TILE if row_tile >= ROW_TILE or k * n <= _WEIGHT_TILE else _WEIGHT_TILE // 4
    tk = _widest(k, _WHOLE_K if k <= _WHOLE_K else most // _WHOLE_K)
    return tk, _widest(n, most // tk) if tk else 0


def can_use_grouped_kernel(rows, experts, out_type=None, row_tile: Optional[int] = None) -> bool:
    """Platform and static shape alone, as ``ops.paged_attention``'s kernels
    are chosen: a TPU, rows and experts of one 16-bit type, a window of whole
    sublane tiles and of whole row tiles (``row_tile``; not given:
    ``_row_tile``'s ``ROW_TILE``, of which the kernel walks any number, or
    every row) of no more than ``_KERNEL_ROWS`` rows, matrices of whole weight
    tiles of whole lanes, and a call whose buffers fit the fast memory a kernel
    may ask for."""
    if jax.default_backend() != "tpu":
        return False
    (r, k), n = rows.shape, experts.shape[-1]
    row_tile = _row_tile(r) if row_tile is None else row_tile
    tk, tn = _weight_tile(k, n, row_tile)
    out_itemsize = jnp.dtype(rows.dtype if out_type is None else out_type).itemsize
    return (
        rows.dtype == experts.dtype and jnp.dtype(rows.dtype).itemsize == 2
        and r % 16 == 0 and r % row_tile == 0 and row_tile <= _KERNEL_ROWS
        and tk > 0 and tn > 0
        and vmem_bytes((row_tile, tk, tn), 2, out_itemsize) <= VMEM_BUDGET
    )


def grouped_matmul(rows, experts, groups, out_type=None, row_tile: Optional[int] = None):
    """``rows`` (R, K), sorted by group, times ``experts`` (G, K, N): the first
    ``groups[0]`` rows by expert 0 and so on; rows past the last group come
    out as whatever was there. On a TPU the Pallas grouped matmul
    (``ops/grouped_matmul.py``) at a stated tiling, under row tiles of
    ``row_tile`` rows (``expert_layer`` knows the rows an expert gets; not
    given, ``_row_tile`` of the operand's rows alone); elsewhere, and for
    shapes the kernel does not take, ``jax.lax.ragged_dot`` (whose own lowering
    on a TPU streams an expert under 512 x 512 tiles: PERF.md section 6, PR
    38)."""
    out_type = rows.dtype if out_type is None else out_type
    row_tile = _row_tile(rows.shape[0]) if row_tile is None else row_tile
    if can_use_grouped_kernel(rows, experts, out_type, row_tile):
        tiling = (row_tile, *_weight_tile(rows.shape[1], experts.shape[-1], row_tile))
        return _gmm(rows, experts, groups, preferred_element_type=out_type, tiling=tiling)
    return jax.lax.ragged_dot(rows, experts, groups, preferred_element_type=out_type)


# A prompt's combine, past this many bytes of gathered rows (every (token,
# choice) row, float32), walks the tokens' held choices in a loop; under it, a
# decode step's and the small buckets', one gather brings them all, and they
# stay in fast memory (K x T x d x 4: Granite's 256 bucket 42 MB, Kimi-K2's
# 58, LFM2's 1,024 bucket 34; PERF.md section 6, PR 58)
_GATHERED_BYTES = 64 << 20


def combine(results, rank, is_held, weights):
    """A token's weighted sum of its held choices' result rows, float32:
    ``results`` (R, d) in the sorted order the grouped matmuls wrote them,
    ``rank`` (T, K) the row of each (token, choice), ``is_held`` and
    ``weights`` (T, K) -> (T, d). The parts add in the order of the choices,
    whoever else is in the batch; a choice no held expert owns adds exactly
    zero, whatever lies at its rank (a row nobody wrote).

    Two walks of the same sum, chosen by static shape. Few rows (a decode
    step, a small bucket): one gather of every (choice, token) row,
    choice-major, and the sum down its leading axis: two operations, whose rows
    stay in fast memory. Many: the j-th held choice of every token in trip j of
    a loop that ends with the token that has the most, so that a chip that
    holds a thirty-second of the experts (a token's held choices: a quarter of
    one in the mean, three at the most) gathers three rows a token and not
    eight. A gather moves a row a copy (~30-80 ns a row of 16 KB on a v5e)
    whether the row is held or not, which is what the loop is for."""
    (t, top_k), d = rank.shape, results.shape[1]
    if top_k * t * d * 4 <= _GATHERED_BYTES:
        rows = results[rank.T.reshape(-1)].reshape(top_k, t, d)
        parts = jnp.where(is_held.T[..., None], weights.T[..., None] * rows, 0.0)
        total = parts[0]
        for k in range(1, top_k):
            total = total + parts[k]
        return total
    nth = jnp.cumsum(is_held, axis=1) - 1  # a held choice's number among its token's held choices

    def trip(j, total):
        mine = is_held & (nth == j)  # one choice a token at the most
        row, w = (jnp.sum(jnp.where(mine, x, 0), axis=1) for x in (rank, weights))
        return total + jnp.where(jnp.any(mine, axis=1)[:, None], w[:, None] * results[row], 0.0)

    return jax.lax.fori_loop(0, jnp.max(jnp.sum(is_held, axis=1)), trip, jnp.zeros((t, d), jnp.float32))


def expert_layer(params: Dict[str, jax.Array], u: jax.Array, *, n_routed: int, top_k: int, scale: float,
                 expert_offset: int = 0, live: Optional[jax.Array] = None, layer=None, rule=route,
                 rows: Optional[jax.Array] = None):
    """``u`` (T, D) -> (y (T, D), counts uint32 in the order ``COUNTS``).

    ``params``: ``router`` (D, n_routed + n_zero), ``router_bias``, and the
    held experts' ``e_gate``/``e_up`` (held, D, F) and ``e_down`` (held, F,
    D): experts ``expert_offset ..`` of the ``n_routed``. ``live`` (T,) bool
    marks the rows that are tokens (padding and empty decode slots route
    nowhere and are not counted). ``counts``: rows sent to held, identity and
    absent experts, held experts with at least one row, the most rows any
    held expert got, the windows walked and the (row tile, expert) pairs a
    grouped call visits. ``rule``: one of the three.

    **What the caller hands in chooses the expert.** With ``e_gate`` it is the
    gated one, ``e_down (silu(e_gate v) * e_up v)``; without, the ungated one of
    two matrices, ``e_down relu(e_up v)^2`` (two grouped calls a window, the
    square taken in float32 ahead of the rounding ``e_down`` reads). ``rows``
    (T, C) is ``v``, what the experts read, where it is not what the router
    reads (experts in a latent: ``v = W_in u``, the caller's matmul): the
    experts are then (held, C, F) and (held, F, C), an identity expert adds
    ``w * v``, and ``y`` is (T, C), the weighted sum the caller projects back.

    The held rows go through the grouped matmuls ``window_rows`` at a
    time, in sorted order, and their float32 result rows stay in that order:
    one window of every row leaves them where the grouped matmul wrote them,
    windows that are walked write theirs one after another. A token then adds
    the rows at its choices' ranks in the sort (``combine``), in the order of
    its choices: its result depends neither on who shares its batch nor on
    the window or the row of it that its rows fell to.

    ``layer``: the expert tensors are all layers' stacked, (layers, held, ..),
    and this is the layer to use (it may be traced). The grouped matmul then
    runs over ``layers x held`` groups of which only this layer's have rows,
    and reads those experts where they lie: cutting a layer out of the stack
    would copy every held expert, touched or not, once a call."""
    t, v = u.shape[0], u if rows is None else rows
    d = v.shape[-1]
    stacks = tuple(params[k] for k in ("e_gate", "e_up", "e_down") if params.get(k) is not None)  # two where no gate
    held_n = stacks[0].shape[0] if layer is None else stacks[0].shape[1]
    with jax.named_scope("router"):
        weights, experts = rule(u, params["router"], params["router_bias"], top_k=top_k, scale=scale)
        alive = jnp.ones((t, 1), bool) if live is None else live[:, None]
        is_zero = (experts >= n_routed) & alive
        local = experts - expert_offset
        is_held = (local >= 0) & (local < held_n) & (experts < n_routed) & alive
    with jax.named_scope("zero"):
        # identity experts: the sum of their weights times the token, no matmul
        y = (jnp.sum(jnp.where(is_zero, weights, 0.0), axis=-1, keepdims=True) * v.astype(jnp.float32))
    with jax.named_scope("experts"):
        # every (token, choice) pair is a row; the held ones sort to the front,
        # by expert, and the grouped matmuls see them a window at a time
        n_rows = t * top_k
        window = window_rows(n_rows, held_n, params["router"].shape[-1])
        group = jnp.where(is_held, local, held_n).reshape(n_rows)
        sizes = jnp.bincount(group, length=held_n + 1)[:held_n].astype(jnp.int32)
        ends = jnp.cumsum(sizes)
        starts, n_held = ends - sizes, ends[-1]
        n_windows = (n_held + window - 1) // window
        # the grouped calls' row tile, from the rows this call sends an expert; a window is whole tiles, so the (tile,
        # expert) pairs the calls visit follow from where an expert's rows start and end in the sort
        tile = _row_tile(window, n_rows / params["router"].shape[-1], stacks[0].shape[-2] * stacks[0].shape[-1])
        n_pairs = jnp.sum(jnp.where(sizes > 0, (ends - 1) // tile - starts // tile + 1, 0))
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        # where each (token, choice) row fell in the sort: the inverse of ``order`` (a second sort: a scatter of
        # 22,528 integers writes them one at a time, four times a sort's time on a v5e)
        rank = jnp.argsort(order).astype(jnp.int32).reshape(t, top_k)
        order = jnp.pad(order, (0, -n_rows % window))  # to whole windows
        if layer is not None:
            stacks = tuple(w.reshape(-1, *w.shape[2:]) for w in stacks)
        *e_in, e_down = stacks

        def walk(w, results):
            """Window ``w``'s rows through the grouped matmuls; their float32
            results, in the sorted order, to the window's rows of ``results``
            (None: the window is the whole sort, and they are the results)."""
            at = w * window + jnp.arange(window, dtype=jnp.int32)
            # rows past the last held one are no token's: each reads the last token, and its result is never read
            row = jnp.where(at < n_held, jax.lax.dynamic_slice(order, (w * window,), (window,)), n_rows - 1)
            mine = v[row // top_k]
            groups = jnp.clip(ends - w * window, 0, window) - jnp.clip(starts - w * window, 0, window)
            if layer is not None:
                groups = jax.lax.dynamic_update_slice(
                    jnp.zeros((e_down.shape[0],), jnp.int32), groups, (layer * held_n,))
            if len(e_in) == 2:
                hidden = swiglu(*(grouped_matmul(mine, e, groups, row_tile=tile) for e in e_in))
            else:
                hidden = relu2(grouped_matmul(mine, e_in[0], groups, jnp.float32, tile)).astype(mine.dtype)
            res = grouped_matmul(hidden, e_down, groups, jnp.float32, tile)
            return res if results is None else jax.lax.dynamic_update_slice(results, res, (w * window, 0))

        # a window that takes every row (or more: whole row tiles) is the whole walk, is traced without a loop and
        # leaves its results where the grouped matmul wrote them; windows that are walked write theirs one after
        # another, each one contiguous copy, into rows nobody has written (no window: none is ever read back)
        if window >= n_rows:
            results = walk(0, None)
        else:
            results = jax.lax.fori_loop(0, n_windows, walk, unwritten((order.shape[0], d), jnp.float32))
        y = y + combine(results, rank, is_held, weights)
    counts = jnp.stack([
        jnp.sum(is_held), jnp.sum(is_zero), jnp.sum(alive & ~is_held & ~is_zero), jnp.sum(sizes > 0),
        jnp.max(sizes), n_windows, n_pairs,
    ]).astype(jnp.uint32)
    return y.astype(u.dtype), counts
