"""The selective state-space recurrence (``ops/selective_scan.py``) against
the token recurrence written out here: the prompt's kernel (interpret mode) and
the scan it stands in for, the decode step's kernel over a pool of rows with
its ``advance``, a padded position passing the state through, and the short
convolution's optional bias; Mamba-2's coarser state over the same update
(``B`` and ``C`` a group of channels, the decay given a head) and its prompt in
chunks of matrix products (``ops/ssd.py``) against the same token walk. CPU,
float32."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from ray_tpu.ops import selective_scan as S  # noqa: E402
from ray_tpu.ops.gated_delta import short_conv_step  # noqa: E402
from ray_tpu.ops.ssd import ssd_chunked  # noqa: E402

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


# -- Mamba-2: B and C a group of channels, the decay a head's ---------------------------------


def test_the_update_at_phis_shape_is_bit_for_bit_what_it_was_however_it_is_asked():
    """(16, 5120) with ``B``, ``C`` (B, N) and the layer's ``A``: one pass over
    the state dimensions, the arithmetic the kernel always had. One group given
    as (B, 1, N) is the same call; the decay given (``exp(Dl A)`` computed by
    the caller, here with a column's ``A`` the same down the column) is the
    same product: both bit for bit."""
    rng = np.random.default_rng(6)
    d, rows = 5120, 3
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    c, dl, bm, cm = f(rows, d), jnp.asarray(rng.uniform(0.001, 0.1, size=(rows, d)), jnp.float32), f(rows, N), f(rows, N)
    a = -jnp.broadcast_to(jnp.asarray(rng.uniform(0.5, 16.0, size=(1, d)), jnp.float32), (N, d))
    pool = f(2, 4, N, d)
    where, advance = jnp.asarray([3, 1, 0]), jnp.asarray([True, True, False])
    y, new = S.selective_scan_update(pool, 1, where, advance, c, dl, bm, cm, jnp.stack([a, a]), interpret=True)
    want_y, want = S.ssm_step(pool[1, where], c, dl, bm, cm, a, advance)
    np.testing.assert_allclose(y, want_y, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(new[1, where], want, atol=1e-6, rtol=1e-6)
    y1, new1 = S.selective_scan_update(pool, 1, where, advance, c, dl, bm[:, None], cm[:, None], jnp.stack([a, a]), interpret=True)
    y2, new2 = S.selective_scan_update(pool, 1, where, advance, c, dl, bm, cm, decay=jnp.exp(dl * a[0]), interpret=True)
    for got_y, got in ((y1, new1), (y2, new2)):
        np.testing.assert_array_equal(np.asarray(got_y), np.asarray(y))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(new))


def mamba2_inputs(seed, t, heads=4, p=128, groups=2, n=256, b=B):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    dt = jnp.asarray(rng.uniform(0.001, 0.1, size=(b, t, heads)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 16.0, size=(heads,)), jnp.float32)
    return f(b, t, heads, p), dt, a, f(b, t, groups, n) * 0.3, f(b, t, groups, n) * 0.3


def mamba2_recurrence(x, dt, a, bm, cm):
    """S_h <- exp(dt_h A_h) S_h + B_g (dt_h x_h)^T; y_h = S_h^T C_g, g = h // (H / G), in numpy float64. -> (y (B, T, H,
    P), the last state (B, N, H x P))."""
    x, dt, a, bm, cm = (np.asarray(t, np.float64) for t in (x, dt, a, bm, cm))
    b, t, heads, p = x.shape
    groups, n = bm.shape[2:]
    of = np.arange(heads) // (heads // groups)
    state, ys = np.zeros((b, heads, n, p)), []
    for i in range(t):
        state = (np.exp(dt[:, i] * a)[:, :, None, None] * state
                 + bm[:, i][:, of][..., None] * (dt[:, i][..., None] * x[:, i])[:, :, None, :])
        ys.append(np.einsum("bhnp,bhn->bhp", state, cm[:, i][:, of]))
    return np.stack(ys, axis=1), state.transpose(0, 2, 1, 3).reshape(b, n, heads * p)


def test_the_update_with_two_groups_and_a_decay_a_head_is_the_mamba2_step():
    """(256, 512) of a (256, 4096) state's layout: four heads of 128 channels,
    two groups, four passes of 64 state dimensions a lane tile."""
    x, dt, a, bm, cm = mamba2_inputs(7, 6)
    _, after = mamba2_recurrence(x[:, :5], dt[:, :5], a, bm[:, :5], cm[:, :5])
    want_y, want = mamba2_recurrence(x, dt, a, bm, cm)
    heads, p = x.shape[2:]
    pool = jnp.zeros((2, 4, 256, heads * p), jnp.float32).at[1, 2].set(after[0]).at[1, 3].set(after[1]).at[0, 2].set(1.0)
    rows, advance = jnp.asarray([2, 3, 0]), jnp.asarray([True, True, False])
    last = lambda t: jnp.concatenate([t[:, 5], t[:1, 5]])  # noqa: E731 - two sequences and an inactive slot
    over = lambda t: jnp.repeat(t, p, axis=-1)  # noqa: E731 - a head's number over its channels
    args = (last(x).reshape(3, -1), over(last(dt)), last(bm), last(cm))
    decay = over(jnp.exp(last(dt) * a))
    y, new = S.selective_scan_update(pool, 1, rows, advance, *args, decay=decay, interpret=True)
    np.testing.assert_allclose(y[:2], want_y[:, 5].reshape(2, -1), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(new[1, rows[:2]], want, atol=2e-6, rtol=2e-6)
    step_y, step = S.ssm_step(pool[1, rows], *args, None, advance, decay=decay)
    np.testing.assert_allclose(y, step_y, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(new[1, rows], step, atol=1e-6, rtol=1e-6)
    # B and C of the other group is another result: the groups are not mixed up where they are the same size
    swapped, _ = S.selective_scan_update(pool, 1, rows, advance, *args[:2], args[2][:, ::-1], args[3][:, ::-1], decay=decay,
                                         interpret=True)
    assert np.abs(np.asarray(swapped - y)[:2]).max() > 0.05
    for layer, row in ((1, 0), (0, 2), (1, 1)):  # the null row, another layer's, a row nobody named
        np.testing.assert_array_equal(np.asarray(new[layer, row]), np.asarray(pool[layer, row]))
    with pytest.raises(ValueError, match="whole lane tiles"):
        S.selective_scan_update(pool[..., :384], 1, rows, advance, *(t[..., :384] for t in args[:2]), *args[2:],
                                decay=decay[..., :384], interpret=True)


GRANITE = dict(heads=4, p=64, groups=1, n=128)  # the published head shapes in small: a head of half a lane tile, one group
FALCON = dict(heads=4, p=128, groups=2, n=256)  # a head a lane tile, two groups of two heads


@pytest.mark.parametrize("t, chunk, shape, form", [
    *((t, chunk, dict(p=16, n=32), "products") for t, chunk in [(24, 8), (8, 8), (5, 8), (256, 128), (64, 128)]),
    # the tile from the shapes: a bucket of two tiles and one of half a tile, B 1 and 2, in both forms
    *((t, None, dict(shape, b=b), form) for shape in (GRANITE, FALCON) for t, b in [(256, 1), (256, 2), (64, 2), (64, 1)]
      for form in ("products", "kernel")),
    (384, None, dict(heads=8, p=64, groups=2, n=128, b=1), "kernel"),  # three tiles, two blocks of lanes a group
    (512, 256, dict(GRANITE, b=1), "kernel"),  # a tile that is given
])
def test_a_prompt_in_chunks_of_matrix_products_is_the_token_walk(t, chunk, shape, form):
    """Chunk edges: a prompt of whole chunks, of one, of less than one; the tile
    of 128 over two chunks and over half of one; the kernel under the
    interpreter at heads that share a lane tile and heads that fill one."""
    x, dt, a, bm, cm = mamba2_inputs(8, t, **shape)
    y, state = ssd_chunked(x, dt, a, bm, cm, chunk, kernel=form == "kernel", interpret=True)
    want_y, want_state = mamba2_recurrence(x, dt, a, bm, cm)
    np.testing.assert_allclose(y, want_y, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(state, want_state, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("form", ["products", "kernel"])
def test_a_prompts_chunked_form_at_granites_shape_keeps_no_heads_decays_and_turns_no_state(form):
    """(1, 1024, 128, 64), N 128, traced and not run: the parent's ``L`` was
    (chunks, heads, 256, 256) float32, 134 MB a layer, and its state (heads, N,
    P) until a transposition at the end. Nothing over 40 MB is made in either
    form (the kernel's blocks are its own), and no array with the heads, the
    state dimension and a head's channels apart is transposed."""
    import collections

    heads, p, n, s = 128, 64, 128, 1024
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    jaxpr = jax.make_jaxpr(lambda *a: ssd_chunked(*a, kernel=form == "kernel"))(
        f32(1, s, heads, p), f32(1, s, heads), f32(heads), f32(1, s, 1, n), f32(1, s, 1, n))
    apart = collections.Counter([heads, n, p])
    largest, kernels = 0, 0

    def walk(jaxpr):
        nonlocal largest, kernels
        for eqn in jaxpr.eqns:
            kernels += eqn.primitive.name == "pallas_call"
            for v in eqn.outvars:
                largest = max(largest, int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize)
            if eqn.primitive.name == "transpose":
                assert apart - collections.Counter(eqn.invars[0].aval.shape), eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert kernels == (form == "kernel")
    assert 33e6 < largest < 40e6  # x and y, 33.5 MB (and the other form's ``L`` at its tile of 64); the parent's 134 MB
    assert [v.aval.shape for v in jaxpr.jaxpr.outvars] == [(1, s, heads, p), (1, n, heads * p)]


def test_a_padded_tail_and_a_chunk_of_padding_alone_pass_the_chunked_state_through():
    x, dt, a, bm, cm = mamba2_inputs(9, 32, p=16, n=32)
    live = 19  # two whole chunks of 8, three tokens of a third, a fourth of padding alone
    dt = dt.at[:, live:].set(0.0)
    y, padded = ssd_chunked(x, dt, a, bm, cm, 8)
    y24, at24 = ssd_chunked(*(t[:, :24] for t in (x, dt)), a, *(t[:, :24] for t in (bm, cm)), 8)
    want_y, exact = mamba2_recurrence(*(t[:, :live] for t in (x, dt)), a, *(t[:, :live] for t in (bm, cm)))
    np.testing.assert_allclose(padded, exact, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(y[:, :live], want_y, atol=2e-5, rtol=2e-5)
    # a chunk of padding alone multiplies the state by exp(0) and adds 0: the fourth chunk changes nothing (the
    # tolerance is for the first three, which a backend may sum in another order in a batch of four chunks than of three)
    np.testing.assert_allclose(padded, at24, atol=1e-7, rtol=1e-6)
    np.testing.assert_allclose(y[:, :24], y24, atol=1e-7, rtol=1e-6)
    with pytest.raises(ValueError, match="not whole chunks"):
        ssd_chunked(*(t[:, :20] for t in (x, dt)), a, *(t[:, :20] for t in (bm, cm)), 8)
