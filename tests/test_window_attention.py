"""Differential attention's three forms (``ops/window_attention.py``) and the
packed-pair call of the paged kernel over a flat pool, against differential
attention written out with masks: the banded prefill, the ring kernel
(interpret mode) with rings part full, full and wrapped, the row it writes into
its ring against a scatter of that row (``models/phi4flash.py:_write_spans``)
wherever the row lies in the sublane tiles, a decode step of the model with the
kernel in it against the step that scatters and gathers, and
``paged_decode_attention`` on ``[q1; 0]`` and ``[0; q2]`` at a head count off
the sublane tile. CPU, float32 (and the rings' bfloat16 where a row's place in
a tile of 16 is the point)."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import functools  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from ray_tpu.models import paged, phi4flash as M  # noqa: E402
from ray_tpu.ops import window_attention as W  # noqa: E402
from ray_tpu.ops.paged_attention import can_use_paged_kernel, chunk_blocks_for, paged_decode_attention  # noqa: E402

P, G, HALF = 4, 2, 8  # query pairs, K/V pairs, a head's values: a pair is 16 wide
SCALE, LAM = HALF ** -0.5, 0.37


def normal(seed, *shape):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), jnp.float32)


def masked(qp, k, v, sees, lam=LAM):
    """Differential attention a head at a time: qp (Q, P, 2d), k, v (M, G, 2d), sees (Q, M) bool."""
    qp, k, v = (np.asarray(x, np.float64) for x in (qp, k, v))
    out = np.zeros(qp.shape)
    for j in range(qp.shape[1]):
        g = j // (qp.shape[1] // k.shape[1])
        weights = []
        for half in (slice(0, HALF), slice(HALF, 2 * HALF)):
            s = np.where(sees, qp[:, j, half] @ k[:, g, half].T * SCALE, -np.inf)
            e = np.exp(s - s.max(-1, keepdims=True))
            weights.append(e / e.sum(-1, keepdims=True))
        out[:, j] = (weights[0] - lam * weights[1]) @ v[:, g]
    return out


@pytest.mark.parametrize("window", [None, 8, 16])
@pytest.mark.parametrize("s", [32, 8])
def test_a_prompts_banded_blocks_are_masked_differential_attention(window, s):
    q, k, v = normal(1, 1, s, P, 2 * HALF), normal(2, 1, s, G, 2 * HALF), normal(3, 1, s, G, 2 * HALF)
    got = W.diff_attention_prefill(q, k, v, LAM, scale=SCALE, window=window, block=8)
    pos = np.arange(s)
    sees = pos[None, :] <= pos[:, None]
    if window:
        sees &= pos[None, :] > pos[:, None] - window
    np.testing.assert_allclose(got[0], masked(q[0], k[0], v[0], sees), atol=2e-5, rtol=2e-5)


def test_a_prompt_that_is_not_whole_blocks_is_refused():
    with pytest.raises(ValueError, match="not whole blocks"):
        W.diff_attention_prefill(normal(1, 1, 12, P, 16), normal(2, 1, 12, G, 16), normal(3, 1, 12, G, 16), LAM,
                                 scale=SCALE, window=8)


def attend(qp, ring_k, ring_v, layer, rows, live, kv_pairs=G):
    """The kernel over rings that already hold the step's row (a step
    dispatched again): each sequence's newest row handed over as the new one.
    The rings come back as they went in. -> ``o``."""
    at = jnp.maximum(live - 1, 0)
    mine = (at[:, None] * kv_pairs + jnp.arange(kv_pairs))[:, :, None]
    new = [jnp.take_along_axis(r[layer, rows], mine, axis=1) for r in (ring_k, ring_v)]
    o, *rings = W.ring_window_attention(qp, *new, ring_k, ring_v, layer, rows, live, at, LAM, kv_pairs=kv_pairs,
                                        scale=SCALE, interpret=True)
    for got, want in zip(rings, (ring_k, ring_v)):
        np.testing.assert_array_equal(got, want)
    return o


@pytest.mark.parametrize("live", [(8, 3, 0), (1, 8, 5)])
def test_the_ring_kernel_is_masked_differential_attention_over_the_live_rows(live):
    window = 8
    qp = normal(4, 3, P, 2 * HALF)
    ring_k, ring_v = normal(5, 2, 4, window * G, 2 * HALF), normal(6, 2, 4, window * G, 2 * HALF)
    rows, live = jnp.asarray([2, 3, 0]), jnp.asarray(live)
    got = attend(qp, ring_k, ring_v, 1, rows, live)
    for b in range(3):
        k, v = (np.asarray(r[1, rows[b]]).reshape(window, G, 2 * HALF) for r in (ring_k, ring_v))
        if int(live[b]):
            want = masked(qp[b][None], k, v, (np.arange(window) < int(live[b]))[None])[0]
            np.testing.assert_allclose(got[b], want, atol=2e-5, rtol=2e-5)
        else:
            assert not np.asarray(got[b]).any()  # an inactive slot: zeros, not 0/0
    # the rows form gives the same over the same rows (the decode step's path off the TPU)
    k, v = (r[1, rows].reshape(3, window, G, 2 * HALF) for r in (ring_k, ring_v))
    same = W.diff_attention_rows(qp, k, v, jnp.arange(window)[None, :] < live[:, None], LAM, scale=SCALE)
    np.testing.assert_allclose(got, same, atol=2e-5, rtol=2e-5)


def test_a_ring_that_wrapped_is_the_window_whatever_order_its_rows_lie_in():
    """No rotary: position p at p % window. A sequence at position 19 of a window of 8 holds positions 12..19 at
    rows 4, 5, 6, 7, 0, 1, 2, 3, and attention over the ring is attention over the window."""
    window, n = 8, 20
    k, v = normal(7, n, G, 2 * HALF), normal(8, n, G, 2 * HALF)
    qp = normal(9, 1, P, 2 * HALF)
    ring = [jnp.zeros((1, 2, window * G, 2 * HALF), jnp.float32).at[0, 1].set(
        jnp.concatenate([x[16:20], x[12:16]]).reshape(window * G, 2 * HALF)) for x in (k, v)]
    got = attend(qp, *ring, 0, jnp.asarray([1]), jnp.asarray([window]))
    want = masked(qp, k, v, ((np.arange(n) > 19 - window) & (np.arange(n) <= 19))[None])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


# A row of ``kv_pairs`` pairs lies at rows ``at x kv_pairs`` on: never a whole sublane tile where the pairs are five
# (float32: tiles of 8) or the published ten (bfloat16: tiles of 16), across two at ``at`` 1, and at the ring's last
# row in the tiles whose start is clamped. Sequences (state rows, ``at``, ``live``) of a batch of three; row 0 is null.
WRITES = {
    "at_the_rings_start": ((2, 3, 1), (0, 0, 0), (1, "W", 1)),
    "pairs_across_two_tiles": ((2, 3, 1), (1, 1, 1), (2, "W", 2)),
    "the_rings_last_row": ((1, 2, 3), ("W-1", "W-1", "W-1"), ("W", "W", "W")),
    "a_ring_that_wrapped": ((3, 1, 2), (3, 5, 2), ("W", "W", "W")),
    "a_ring_shorter_than_the_window": ((2, 3, 1), (2, 4, 0), (3, 5, 1)),
    "an_inactive_slot_among_live_ones": ((2, 0, 3), (3, 6, 1), ("W", 0, 2)),
    "two_calls_at_one_position": ((2, 3, 1), (1, "W-1", 4), (2, "W", "W")),
}


@pytest.mark.parametrize("case", list(WRITES))
@pytest.mark.parametrize("dtype, window, kv_pairs, wide", [("float32", 8, 5, 2 * HALF), ("bfloat16", 16, 10, 128)])
def test_the_ring_kernel_writes_its_row_as_a_scatter_would_and_attends_over_it(case, dtype, window, kv_pairs, wide):
    """The rings that come back are bit for bit ``_write_spans``' (the live
    sequences' rows written, the null row and every other row as they came),
    and ``o`` is ``diff_attention_rows`` over them: the new row is scored where
    the stale one lay."""
    pairs, layer, scale = 2 * kv_pairs, 1, (wide // 2) ** -0.5
    rows, at, live = (jnp.asarray([{"W": window, "W-1": window - 1}.get(x, x) for x in xs]) for xs in WRITES[case])
    qp, new_k, new_v = normal(13, 3, pairs, wide), normal(14, 3, kv_pairs, wide), normal(15, 3, kv_pairs, wide)
    rings = [normal(seed, 2, 4, window * kv_pairs, wide).astype(dtype) for seed in (16, 17)]
    kernel = functools.partial(W.ring_window_attention, kv_pairs=kv_pairs, scale=scale, interpret=True)
    o, *got = kernel(qp, new_k, new_v, *rings, layer, rows, live, at, LAM)
    if case == "two_calls_at_one_position":  # the replay: the same row again, the same output
        once, (o, *got) = o, kernel(qp, new_k, new_v, *got, layer, rows, live, at, LAM)
        np.testing.assert_array_equal(o, once)
    held = np.flatnonzero(np.asarray(live))
    want = [M._write_spans(ring, (layer, rows[held]), at[held] * kv_pairs, new[held]) for ring, new in zip(rings, (new_k, new_v))]
    for g, w, ring in zip(got, want, rings):
        assert g.dtype == ring.dtype
        np.testing.assert_array_equal(np.asarray(g, np.float32), np.asarray(w, np.float32))
        np.testing.assert_array_equal(np.asarray(g[:, 0], np.float32), np.asarray(ring[:, 0], np.float32))  # the null row
    k, v = (w[layer, rows].reshape(3, window, kv_pairs, wide) for w in want)
    same = W.diff_attention_rows(qp, k, v, jnp.arange(window)[None, :] < live[:, None], LAM, scale=scale)
    tol = 2e-5 if dtype == "float32" else 2e-2  # bfloat16 weights into the sums: a rounding apart, not a row apart
    np.testing.assert_allclose(o, same, atol=tol, rtol=tol)
    assert not np.asarray(o)[np.asarray(live) == 0].any()


def test_a_decode_step_with_the_kernel_in_it_is_the_step_that_scatters_and_gathers(monkeypatch):
    """``models/phi4flash.py``'s decode step on the path it takes on a TPU (the
    kernel writes the ring's row; here in interpret mode) against the path it
    takes elsewhere (``_write_spans`` and the gathered ring): a prompt shorter
    than the window, then steps through the ring's wrap, an inactive slot
    beside it."""
    cfg = M.Phi4FlashConfig(
        vocab_size=64, hidden_size=64, intermediate_size=96, num_hidden_layers=8, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=64, sliding_window=8, ssm_dt_rank=4, dtype=jnp.float32)
    params = M.init_params(jax.random.PRNGKey(3), cfg)
    block, per_seq, prompt = 4, 9, [5, 9, 2, 44, 17]
    table = jnp.asarray([[0] * per_seq, [1, 2, 3, 4, 5, 6, 7, 8, 1]], jnp.int32)  # slot 0 inactive; blocks 1-8, state row 1
    tokens = jnp.zeros((1, 8), jnp.int32).at[0, :len(prompt)].set(jnp.asarray(prompt))

    def run():
        prefill, decode, _ = paged.make_paged_fns(M.paged_layer, cfg, block_size=block, state_rows=True)
        logits, pool = prefill(params, tokens, table[1:], M.init_paged_pool(cfg, 9, block, 2), jnp.int32(len(prompt)))
        out = []
        for position in range(len(prompt), len(prompt) + 6):  # positions 5..10 of a window of 8
            fed = jnp.asarray([0, int(jnp.argmax(logits[-1]))], jnp.int32)
            logits, pool = decode(params, fed, jnp.asarray([0, position], jnp.int32), table, pool, jnp.asarray([False, True]))
            out.append(np.asarray(logits[1]))
        return np.stack(out), jax.tree.map(np.asarray, pool)

    gathered, pool = run()
    monkeypatch.setattr(M, "can_use_ring_kernel", lambda *_: True)
    traced = []
    monkeypatch.setattr(M, "ring_window_attention", lambda *a, **kw: traced.append(a[0].shape) or W.ring_window_attention(
        *a, **kw, interpret=True))
    kernel, kernel_pool = run()
    assert traced == [(2, cfg.q_pairs, cfg.pair_dim)]  # the window section's one trace, in the decode step alone
    np.testing.assert_allclose(kernel, gathered, atol=2e-4, rtol=2e-4)
    for name in ("ring_k", "ring_v"):
        np.testing.assert_allclose(kernel_pool[name][:, 1], pool[name][:, 1], atol=2e-5, rtol=2e-5)
        assert np.abs(kernel_pool[name][:, 1]).min(axis=-1).all()  # every row of the ring written by now
        assert not kernel_pool[name][:, 0].any()  # the null row: the inactive slot wrote nothing


@pytest.mark.parametrize("kv_pairs, pairs", [(2, 4), (5, 10)])
def test_the_paged_kernel_on_packed_pairs_over_a_flat_pool_is_differential_attention(kv_pairs, pairs):
    """Ten K/V heads of 128 are no whole sublane tile: the pool is flat, a slot's pairs consecutive rows."""
    bs, nb, mb = 4, 16, 5
    qp = normal(10, 3, pairs, 2 * HALF)
    pool = jnp.stack([normal(11, 1, nb * bs * kv_pairs, 2 * HALF), normal(12, 1, nb * bs * kv_pairs, 2 * HALF)], axis=1)
    tables = jnp.asarray([[3, 7, 2, 0, 0], [5, 1, 9, 11, 4], [0] * 5], jnp.int32)
    lengths = jnp.asarray([9, 18, 0], jnp.int32)
    packed = jnp.stack(W.split_queries(qp), axis=2).reshape(3, 2 * pairs, 2 * HALF)
    o = paged_decode_attention(packed, pool, 0, tables, lengths, block_size=bs, kv_heads=kv_pairs, scale=SCALE,
                               interpret=True).reshape(3, pairs, 2, 2 * HALF)
    got = o[:, :, 0] - LAM * o[:, :, 1]
    slots = np.asarray((tables[:, :, None] * bs + jnp.arange(bs)).reshape(3, -1))
    for b in range(2):
        k, v = (np.asarray(x).reshape(nb * bs, kv_pairs, 2 * HALF)[slots[b]] for x in pool[0])
        want = masked(qp[b][None], k, v, (np.arange(mb * bs) < int(lengths[b]))[None])[0]
        np.testing.assert_allclose(got[b], want, atol=2e-5, rtol=2e-5)
    assert not np.asarray(got[2]).any()


def test_the_kernels_are_chosen_from_platform_and_shape_alone(monkeypatch):
    q = jnp.zeros((48, 1, 40, 128), jnp.bfloat16)
    flat, stored = jnp.zeros((1, 2, 64 * 16 * 10, 128), jnp.bfloat16), jnp.zeros((1, 2, 64 * 16, 10, 128), jnp.bfloat16)
    assert not can_use_paged_kernel(q, flat, 16, 10) and not W.can_use_ring_kernel(512, 10, 128, jnp.bfloat16)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert can_use_paged_kernel(q, flat, 16, 10) and not can_use_paged_kernel(q, stored, 16)  # ten heads: flat or not at all
    assert not can_use_paged_kernel(q, flat, 5, 10) and not can_use_paged_kernel(q[:, :, :39], flat, 16, 10)
    assert W.can_use_ring_kernel(512, 10, 128, jnp.bfloat16) and not W.can_use_ring_kernel(512, 10, 64, jnp.bfloat16)
    # a flat chunk's columns are whole lane tiles: 24 blocks of 160 rows, not the 25 a megabyte takes
    assert chunk_blocks_for(192, 16 * 10 * 128 * 2, whole=4) == 24 and chunk_blocks_for(192, 16 * 16 * 128 * 2) == 16


# -- the plain form: one softmax of grouped queries (K-EXAONE's window layers) ---------------------


def plain(q, k, v, sees, scale):
    """One softmax a head: q (Q, H, d), k, v (M, G, d), sees (Q, M) bool."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    out = np.zeros(q.shape)
    for h in range(q.shape[1]):
        g = h // (q.shape[1] // k.shape[1])
        s = np.where(sees, q[:, h] @ k[:, g].T * scale, -np.inf)
        e = np.exp(s - s.max(-1, keepdims=True))
        out[:, h] = (e / e.sum(-1, keepdims=True)) @ v[:, g]
    return out


@pytest.mark.parametrize("window", [None, 8, 16])
@pytest.mark.parametrize("s", [32, 8])
def test_a_prompts_banded_blocks_are_masked_plain_attention(window, s):
    d = 2 * HALF
    q, k, v = normal(1, 1, s, P, d), normal(2, 1, s, G, d), normal(3, 1, s, G, d)
    got = W.window_attention_prefill(q, k, v, scale=d ** -0.5, window=window, block=8)
    pos = np.arange(s)
    sees = pos[None, :] <= pos[:, None]
    if window:
        sees &= pos[None, :] > pos[:, None] - window
    np.testing.assert_allclose(got[0], plain(q[0], k[0], v[0], sees, d ** -0.5), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("case", list(WRITES))
@pytest.mark.parametrize("dtype, window, heads, kv_heads, wide", [("float32", 8, 6, 3, 2 * HALF), ("bfloat16", 16, 64, 8, 128)])
def test_the_ring_kernels_plain_form_writes_its_row_and_is_one_softmax_over_the_live_rows(case, dtype, window, heads,
                                                                                       kv_heads, wide):
    """``lam`` None: the queries as they are (64 heads of 128 over 8 K/V heads,
    the published widths, and six over three in tiles of 8), one softmax, no
    second half. The rings come back bit for bit a scatter's, the null row
    untouched; ``o`` is ``window_attention_rows`` over them."""
    layer, scale = 1, wide ** -0.5
    rows, at, live = (jnp.asarray([{"W": window, "W-1": window - 1}.get(x, x) for x in xs]) for xs in WRITES[case])
    q, new_k, new_v = normal(13, 3, heads, wide), normal(14, 3, kv_heads, wide), normal(15, 3, kv_heads, wide)
    rings = [normal(seed, 2, 4, window * kv_heads, wide).astype(dtype) for seed in (16, 17)]
    kernel = functools.partial(W.ring_window_attention, kv_pairs=kv_heads, scale=scale, interpret=True)
    o, *got = kernel(q, new_k, new_v, *rings, layer, rows, live, at, None)
    if case == "two_calls_at_one_position":
        once, (o, *got) = o, kernel(q, new_k, new_v, *got, layer, rows, live, at, None)
        np.testing.assert_array_equal(o, once)
    held = np.flatnonzero(np.asarray(live))
    want = [W.write_spans(ring, (layer, rows[held]), at[held] * kv_heads, new[held]) for ring, new in zip(rings, (new_k, new_v))]
    for g, w, ring in zip(got, want, rings):
        assert g.dtype == ring.dtype
        np.testing.assert_array_equal(np.asarray(g, np.float32), np.asarray(w, np.float32))
        np.testing.assert_array_equal(np.asarray(g[:, 0], np.float32), np.asarray(ring[:, 0], np.float32))  # the null row
    k, v = (w[layer, rows].reshape(3, window, kv_heads, wide) for w in want)
    same = W.window_attention_rows(q, k, v, jnp.arange(window)[None, :] < live[:, None], scale=scale)
    tol = 2e-5 if dtype == "float32" else 2e-2
    assert o.shape == (3, heads, wide)
    np.testing.assert_allclose(o, same, atol=tol, rtol=tol)
    assert not np.asarray(o)[np.asarray(live) == 0].any()
    if dtype == "float32":  # and the rows form is the statement: one softmax a head over the live rows
        for b in held:
            sees = (np.arange(window) < int(live[b]))[None]
            np.testing.assert_allclose(same[b], plain(q[b][None], k[b], v[b], sees, scale)[0], atol=2e-5, rtol=2e-5)


def test_the_two_forms_are_told_apart_by_lam_alone_and_the_differential_calls_trace_as_before():
    """The plain form's call has no ``lam`` operand and half the query rows
    (no split); the differential call's operands are what they were: queries,
    ``lam``, the mask, the rows' index, the new rows, the rings."""
    q, new = normal(1, 2, 4, 16), normal(2, 2, 2, 16)
    rings = [normal(s, 1, 3, 8 * 2, 16) for s in (3, 4)]
    rows, live, at = jnp.asarray([1, 2]), jnp.asarray([3, 8]), jnp.asarray([2, 5])

    def operands(lam):
        jaxpr = jax.make_jaxpr(lambda *a: W.ring_window_attention(*a, 0, rows, live, at, lam, kv_pairs=2, scale=0.25,
                                                                  interpret=True))(q, new, new, *rings)
        (call,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
        return [tuple(v.aval.shape) for v in call.invars]

    both, one = operands(0.3), operands(None)
    assert both[4:6] == [(2, 16, 16), (1, 16)] and one[4] == (2, 8, 16)  # [q1; 0] and [0; q2] padded to 8 each, lam
    assert len(both) == len(one) + 1 and both[6:][1:] == one[5:][1:]  # beyond the mask's rows, the same operands
