"""The selective state-space recurrence (``ops/selective_scan.py``) against
the token recurrence written out here: the prompt's kernel (interpret mode) and
the scan it stands in for, the decode step's kernel over a pool of rows with
its ``advance``, a padded position passing the state through, and the short
convolution's optional bias. CPU, float32."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from ray_tpu.ops import selective_scan as S  # noqa: E402
from ray_tpu.ops.gated_delta import short_conv_step  # noqa: E402

B, T, D, N = 2, 40, 256, 16


def inputs(seed=0, t=T, d=D):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    dl = jnp.asarray(rng.uniform(0.001, 0.1, size=(B, t, d)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 16.0, size=(N, d)), jnp.float32)
    return f(B, t, d), dl, f(B, t, N), f(B, t, N), a


def recurrence(c, dl, bm, cm, a):
    """h_t = exp(Dl_t A) h_{t-1} + (Dl_t c_t) B_t^T; y_t = C_t h_t, in numpy float64."""
    c, dl, bm, cm, a = (np.asarray(x, np.float64) for x in (c, dl, bm, cm, a))
    h, ys = np.zeros((c.shape[0], *a.shape)), []
    for t in range(c.shape[1]):
        h = np.exp(dl[:, t, None, :] * a) * h + (dl[:, t] * c[:, t])[:, None, :] * bm[:, t, :, None]
        ys.append(np.einsum("bnd,bn->bd", h, cm[:, t]))
    return np.stack(ys, axis=1), h


@pytest.mark.parametrize("form", ["scan", "kernel"])
@pytest.mark.parametrize("t, d", [(40, 256), (8, 128), (256, 1024)])
def test_a_prompt_from_an_empty_state_is_the_token_recurrence(form, t, d):
    c, dl, bm, cm, a = inputs(1, t, d)
    y, state = S.selective_scan_chunked(c, dl, bm, cm, a, kernel=form == "kernel", interpret=True)
    want_y, want_state = recurrence(c, dl, bm, cm, a)
    np.testing.assert_allclose(y, want_y, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(state, want_state, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("form", ["scan", "kernel"])
def test_a_padded_position_passes_the_state_through_exactly(form):
    c, dl, bm, cm, a = inputs(2)
    live = 23
    dl = dl.at[:, live:].set(0.0)
    _, padded = S.selective_scan_chunked(c, dl, bm, cm, a, kernel=form == "kernel", interpret=True)
    _, exact = S.selective_scan_chunked(*(x[:, :24] for x in (c, dl, bm, cm)), a, kernel=form == "kernel", interpret=True)
    np.testing.assert_array_equal(np.asarray(padded), np.asarray(exact))  # position 23 is padding in both


def test_a_prompt_that_is_not_whole_chunks_is_refused():
    c, dl, bm, cm, a = inputs(3, t=136)
    with pytest.raises(ValueError, match="not whole chunks"):
        S.selective_scan_chunked(c, dl, bm, cm, a, kernel=True, interpret=True)


def test_the_decode_steps_kernel_updates_the_named_rows_in_place_and_a_replay_reads():
    c, dl, bm, cm, a = inputs(4)
    _, after = recurrence(c, dl, bm, cm, a)
    decays = jnp.stack([a, 0.5 * a, 2.0 * a])
    pool = jnp.zeros((3, 5, N, D), jnp.float32).at[1, 2].set(after[0]).at[1, 4].set(after[1]).at[2, 2].set(1.0)
    rows, advance = jnp.asarray([2, 4, 0]), jnp.asarray([True, False, False])
    token = [jnp.concatenate([x[:, 0], x[:1, 0]]) for x in (c, dl, bm, cm)]  # two sequences and an inactive slot
    y, new = S.selective_scan_update(pool, 1, rows, advance, *token, decays, interpret=True)
    want_y, want = S.ssm_step(pool[1, rows], *token, decays[1], advance)
    np.testing.assert_allclose(y, want_y, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(new[1, rows], want, atol=1e-6, rtol=1e-6)
    # the row that advanced changed; the replayed row and the null row did not; y of a replay is the stored state's
    assert np.abs(np.asarray(new[1, 2] - pool[1, 2])).max() > 1e-4
    for layer, row in ((1, 4), (1, 0), (0, 2), (2, 2), (1, 1)):
        np.testing.assert_array_equal(np.asarray(new[layer, row]), np.asarray(pool[layer, row]))
    np.testing.assert_allclose(y[1], S.ssm_read(pool[1, 4][None], token[3][1:2])[0], atol=1e-5, rtol=1e-5)
    # the same step once more, every row a replay: the pool stays, and the first row's y is what its update gave
    y2, newer = S.selective_scan_update(new, 1, rows, jnp.zeros((3,), bool), *token, decays, interpret=True)
    np.testing.assert_array_equal(np.asarray(newer), np.asarray(new))
    np.testing.assert_array_equal(np.asarray(y2[0]), np.asarray(y[0]))


def test_the_kernels_are_chosen_from_platform_and_shape_alone(monkeypatch):
    import jax

    assert not S.can_use_selective_scan_kernel(5120, 16)  # the CPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert S.can_use_selective_scan_kernel(5120, 16) and not S.can_use_selective_scan_kernel(5000, 16)
    assert not S.can_use_selective_scan_kernel(5120, 12)


@pytest.mark.parametrize("bias", [None, "given"])
def test_the_short_convolution_takes_a_bias_or_none(bias):
    rng = np.random.default_rng(5)
    k, ch = 4, 8
    windows = jnp.asarray(rng.normal(size=(3, k * ch)), jnp.float32)
    u, w = jnp.asarray(rng.normal(size=(2, ch)), jnp.float32), jnp.asarray(rng.normal(size=(k, ch)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(ch,)), jnp.float32) if bias else None
    owner = jnp.asarray([[False, False], [True, False], [False, True]])
    out, new = short_conv_step(windows, u, w, owner, jnp.asarray([False, True, True]), b)
    shifted = np.concatenate([np.asarray(windows)[1:, ch:], np.asarray(u)], axis=-1)
    taps = sum(shifted[:, j * ch:(j + 1) * ch] * np.asarray(w)[j] for j in range(k)) + (0 if b is None else np.asarray(b))
    np.testing.assert_allclose(out, taps / (1 + np.exp(-taps)), atol=1e-5)
    np.testing.assert_allclose(new[1:], shifted, atol=1e-6)
