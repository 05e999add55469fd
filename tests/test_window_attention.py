"""Differential attention's three forms (``ops/window_attention.py``) and the
packed-pair call of the paged kernel over a flat pool, against differential
attention written out with masks: the banded prefill, the ring kernel
(interpret mode) with rings part full, full and wrapped, and
``paged_decode_attention`` on ``[q1; 0]`` and ``[0; q2]`` at a head count off
the sublane tile. CPU, float32."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

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


@pytest.mark.parametrize("live", [(8, 3, 0), (1, 8, 5)])
def test_the_ring_kernel_is_masked_differential_attention_over_the_live_rows(live):
    window = 8
    qp = normal(4, 3, P, 2 * HALF)
    ring_k, ring_v = normal(5, 2, 4, window * G, 2 * HALF), normal(6, 2, 4, window * G, 2 * HALF)
    rows, live = jnp.asarray([2, 3, 0]), jnp.asarray(live)
    got = W.ring_window_attention(qp, ring_k, ring_v, 1, rows, live, LAM, kv_pairs=G, scale=SCALE, interpret=True)
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
    got = W.ring_window_attention(qp, *ring, 0, jnp.asarray([1]), jnp.asarray([window]), LAM, kv_pairs=G, scale=SCALE,
                                  interpret=True)
    want = masked(qp, k, v, ((np.arange(n) > 19 - window) & (np.arange(n) <= 19))[None])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kv_pairs, pairs", [(2, 4), (5, 10)])
def test_the_paged_kernel_on_packed_pairs_over_a_flat_pool_is_differential_attention(kv_pairs, pairs):
    """Ten K/V heads of 128 are no whole sublane tile: the pool is flat, a slot's pairs consecutive rows."""
    bs, nb, mb = 4, 16, 5
    qp = normal(10, 3, pairs, 2 * HALF)
    pool_k, pool_v = normal(11, 1, nb * bs * kv_pairs, 2 * HALF), normal(12, 1, nb * bs * kv_pairs, 2 * HALF)
    tables = jnp.asarray([[3, 7, 2, 0, 0], [5, 1, 9, 11, 4], [0] * 5], jnp.int32)
    lengths = jnp.asarray([9, 18, 0], jnp.int32)
    packed = jnp.stack(W.split_queries(qp), axis=2).reshape(3, 2 * pairs, 2 * HALF)
    o = paged_decode_attention(packed, pool_k, pool_v, 0, tables, lengths, block_size=bs, kv_heads=kv_pairs, scale=SCALE,
                               interpret=True).reshape(3, pairs, 2, 2 * HALF)
    got = o[:, :, 0] - LAM * o[:, :, 1]
    slots = np.asarray((tables[:, :, None] * bs + jnp.arange(bs)).reshape(3, -1))
    for b in range(2):
        k, v = (np.asarray(x[0]).reshape(nb * bs, kv_pairs, 2 * HALF)[slots[b]] for x in (pool_k, pool_v))
        want = masked(qp[b][None], k, v, (np.arange(mb * bs) < int(lengths[b]))[None])[0]
        np.testing.assert_allclose(got[b], want, atol=2e-5, rtol=2e-5)
    assert not np.asarray(got[2]).any()


def test_the_kernels_are_chosen_from_platform_and_shape_alone(monkeypatch):
    q = jnp.zeros((48, 1, 40, 128), jnp.bfloat16)
    flat, stored = jnp.zeros((1, 64 * 16 * 10, 128), jnp.bfloat16), jnp.zeros((1, 64 * 16, 10, 128), jnp.bfloat16)
    assert not can_use_paged_kernel(q, flat, 16, 10) and not W.can_use_ring_kernel(512, 10, 128, jnp.bfloat16)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert can_use_paged_kernel(q, flat, 16, 10) and not can_use_paged_kernel(q, stored, 16)  # ten heads: flat or not at all
    assert not can_use_paged_kernel(q, flat, 5, 10) and not can_use_paged_kernel(q[:, :, :39], flat, 16, 10)
    assert W.can_use_ring_kernel(512, 10, 128, jnp.bfloat16) and not W.can_use_ring_kernel(512, 10, 64, jnp.bfloat16)
    # a flat chunk's columns are whole lane tiles: 24 blocks of 160 rows, not the 25 a megabyte takes
    assert chunk_blocks_for(192, 16 * 10 * 128 * 2, whole=4) == 24 and chunk_blocks_for(192, 16 * 16 * 128 * 2) == 16
