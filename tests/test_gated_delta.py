"""The gated delta rule's three forms (``ops/gated_delta.py``) against the rule
written out a token and a head at a time: the ``jax.numpy`` step, the chunkwise
form of a prompt, the Pallas kernel in interpret mode. CPU, float32."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from ray_tpu.ops import gated_delta as G  # noqa: E402

B, H, DK, DV = 2, 4, 8, 32


def inputs(s, seed=0):
    r = np.random.default_rng(seed)
    q, k = (jnp.asarray(r.normal(size=(B, s, H, DK)), jnp.float32) for _ in range(2))
    v = jnp.asarray(r.normal(size=(B, s, H, DV)), jnp.float32)
    g = -jnp.asarray(r.uniform(0.001, 0.3, size=(B, s, H)), jnp.float32)
    beta = jnp.asarray(r.uniform(0.0, 2.0, size=(B, s, H)), jnp.float32)
    return q, k, v, g, beta


def by_hand(q, k, v, g, beta):
    """The rule a sequence, a head and a token at a time, in float64 numpy."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    b, s = q.shape[:2]
    out, states = np.zeros((b, s, H, DV)), np.zeros((b, H, DK, DV))
    for i in range(b):
        for h in range(H):
            state = np.zeros((DK, DV))
            for t in range(s):
                qt = q[i, t, h] / np.sqrt((q[i, t, h] ** 2).sum() + 1e-6) / np.sqrt(DK)
                kt = k[i, t, h] / np.sqrt((k[i, t, h] ** 2).sum() + 1e-6)
                state = np.exp(g[i, t, h]) * state
                state = state + np.outer(kt, beta[i, t, h] * (v[i, t, h] - state.T @ kt))
                out[i, t, h] = state.T @ qt
            states[i, h] = state
    return out, states.transpose(0, 2, 1, 3).reshape(b, DK, H * DV)  # as the pool keeps a state: (d_k, H x d_v)


def stepped(q, k, v, g, beta):
    state, outs = jnp.zeros((B, DK, H * DV)), []
    for t in range(q.shape[1]):
        o, state = G.gated_delta_step(state, q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t])
        outs.append(o)
    return jnp.stack(outs, axis=1), state


def test_the_step_is_the_rule_written_out():
    args = inputs(11)
    want_o, want_s = by_hand(*args)
    got_o, got_s = stepped(*args)
    np.testing.assert_allclose(got_o, want_o, atol=2e-6)
    np.testing.assert_allclose(got_s, want_s, atol=2e-6)


@pytest.mark.parametrize("length,bucket,chunk", [(64, 64, 16), (21, 32, 8), (21, 32, 64), (5, 16, 16), (40, 64, 16)])
def test_the_chunkwise_form_equals_the_token_recurrence(length, bucket, chunk):
    """Lengths that are whole chunks and lengths that are not: the padded
    positions (decay 1, strength 0) pass the state through, whole chunks of
    padding too. Tolerance 5e-6 absolute on outputs of size ~1: float32 sums in
    another order, and a unit-triangular system solved by blocks a chunk."""
    args = inputs(length, seed=length)
    want_o, want_s = stepped(*args)
    padded = [jnp.pad(x, [(0, 0), (0, bucket - length)] + [(0, 0)] * (x.ndim - 2)) for x in args]
    # what the padding holds must not matter: fill the gates' with numbers that would wreck the state
    padded[3] = padded[3].at[:, length:].set(-3.0)
    padded[4] = padded[4].at[:, length:].set(1.7)
    live = jnp.broadcast_to(jnp.arange(bucket)[None, :] < length, (B, bucket))
    got_o, got_s = G.gated_delta_chunked(*padded, live, chunk=chunk)
    np.testing.assert_allclose(got_o[:, :length], want_o, atol=5e-6)
    np.testing.assert_allclose(got_s, want_s, atol=5e-6)
    with pytest.raises(ValueError, match="not whole chunks"):
        G.gated_delta_chunked(*[x[:, :bucket - 1] for x in padded], live[:, :bucket - 1], chunk=min(chunk, 8))


def chunk_systems(r, c, close=False):
    """Seeded systems of the chunkwise form's own kind, float64: ``L = strict_tril((K beta) K^T . M)`` over B x H
    chunks of ``c`` normalised keys, and right-hand sides ``[V beta | K beta exp(gamma)]``. ``close``: the keys of a
    chunk within 1e-3 of one direction, ``beta`` in 1.9-2.0, decays within 0.001 of 1."""
    k = r.normal(size=(B, H, 1 if close else c, DK)) + (1e-3 * r.normal(size=(B, H, c, DK)) if close else 0.0)
    beta = r.uniform(1.9, 2.0, size=(B, H, c)) if close else r.uniform(0.0, 2.0, size=(B, H, c))
    gamma = np.cumsum(-r.uniform(0.0, 0.001, size=(B, H, c)) if close else -r.uniform(0.001, 0.3, size=(B, H, c)), axis=-1)
    k = k / np.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    kb = k * beta[..., None]
    lower = np.tril(np.einsum("bhik,bhjk->bhij", kb, k) * np.exp(gamma[..., :, None] - gamma[..., None, :]), -1)
    return lower, np.concatenate([r.normal(size=(B, H, c, DV)) * beta[..., None], kb * np.exp(gamma)[..., None]], axis=-1)


@pytest.mark.parametrize("c,close,atol", [(8, False, 2e-6), (16, False, 2e-6), (32, False, 2e-6), (64, False, 2e-6),
                                          (24, False, 2e-6), (20, False, 2e-6), (64, True, 4e-4)])
def test_the_solve_alone_is_the_substitution_in_float64(c, close, atol):
    """``unit_lower_solve`` by itself against ``scipy.linalg.solve_triangular`` in float64, a system at a time: every
    chunk the function takes (8, 16, 32 in the tests above, 64 on the chip) and two that are no power of two (24 and
    20 rows are padded to 32 with rows of the identity). Solutions of size ~10: the parent's ``triangular_solve`` read
    5.3e-7 to 1.6e-6 on these systems where this reads 4.1e-7 to 1.2e-6. The last case is the adversarial chunk by
    itself (solutions up to 42): the parent's substitution a row at a time read 7.6e-5 and blocks of 16 read 2.8e-4;
    in the mean of eight more seeds 1.1e-4 and 2.2e-4 (blocks of 8 1.5e-4, of 32 3.8e-4, the whole inverse built by
    halves 5.8e-4 to 7.7e-4): an inverse of a block is formed, so the wider the block the further from a row-by-row
    substitution, and inside ``gated_delta_chunked`` the chunk scan's own products cover the difference (below)."""
    from scipy.linalg import solve_triangular

    lower, rhs = chunk_systems(np.random.default_rng(c), c, close)
    got = np.asarray(jax.jit(G.unit_lower_solve)(jnp.asarray(lower, jnp.float32), jnp.asarray(rhs, jnp.float32)), np.float64)
    assert got.shape == rhs.shape
    for i in range(B):
        for h in range(H):
            want = solve_triangular(np.eye(c) + lower[i, h], rhs[i, h], lower=True, unit_diagonal=True)
            np.testing.assert_allclose(got[i, h], want, atol=atol)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_chunkwise_form_holds_where_a_chunks_keys_are_nearly_parallel(seed):
    """The adversarial case: 512 positions in chunks of 64, the keys of a chunk within 1e-3 of one direction,
    ``beta`` in 1.9-2.0, ``g`` in -0.001..0, so that ``L`` is nearly 2 everywhere under its diagonal and its powers
    grow by binomials while outputs stay under 10 and the state under 19. Against the rule in float64. Tolerance 2e-4
    absolute, beside what the forms read on these inputs (outputs / state, seeds 0, 1, 2): the parent's
    ``triangular_solve`` 9.5e-5 / 1.04e-4, 7.6e-5 / 8.2e-5, 5.3e-5 / 7.9e-5; this one (block rows of 16) 6.3e-5 /
    1.40e-4, 5.1e-5 / 9.6e-5, 6.4e-5 / 9.0e-5; block rows of 8 5.8e-5 / 1.21e-4, 7.9e-5 / 1.00e-4, 5.9e-5 / 1.06e-4. Over
    six seeds the state reads 8.7e-5 in the parent's mean (7.0e-5 to 1.04e-4), 1.01e-4 in this form's (8.5e-5 to
    1.40e-4), 9.8e-5 with blocks of 8: the forms lie inside one seed's swing of each other, and the tolerance is the
    parent's largest reading with that swing on top. Blocks of 32 read 2.0e-4 in the mean (1.4e-4 to 2.7e-4), the
    whole inverse built by halves 3.4e-4 to 5.4e-4, and the product form ``(I - L)(I + L^2)(I + L^4)..`` NaN."""
    r = np.random.default_rng(seed)
    s, c = 512, G.CHUNK
    k = (r.normal(size=(B, s // c, 1, H, DK)) + 1e-3 * r.normal(size=(B, s // c, c, H, DK))).reshape(B, s, H, DK)
    q, v = r.normal(size=(B, s, H, DK)), r.normal(size=(B, s, H, DV))
    g, beta = -r.uniform(0.0, 0.001, size=(B, s, H)), r.uniform(1.9, 2.0, size=(B, s, H))
    args = [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta)]
    want_o, want_s = by_hand(*args)
    got_o, got_s = jax.jit(G.gated_delta_chunked)(*args, jnp.ones((B, s), bool))
    np.testing.assert_allclose(got_o, want_o, atol=2e-4)
    np.testing.assert_allclose(got_s, want_s, atol=2e-4)


def test_the_kernel_in_interpret_mode_is_the_step_over_the_rows_it_is_given():
    """The pool's rows the call names are updated in place, where ``advance``
    says; every other row, every other layer and the null row stay as they
    were, bit for bit."""
    q, k, v, g, beta = (x[:, 0] for x in inputs(1, seed=3))
    r = np.random.default_rng(9)
    pool = jnp.asarray(r.normal(size=(3, 6, DK, H * DV)), jnp.float32).at[:, 0].set(0.0)
    rows, advance = jnp.asarray([4, 2]), jnp.asarray([True, False])
    want_o, want_s = G.gated_delta_step(pool[1, rows], q, k, v, g, beta, advance)
    got_o, got = G.gated_delta_update(pool, jnp.int32(1), rows, advance, q, k, v, g, beta, interpret=True)
    np.testing.assert_allclose(got_o, want_o, atol=1e-6)
    np.testing.assert_allclose(got[1, rows], want_s, atol=1e-6)
    np.testing.assert_array_equal(got[1, 2], pool[1, 2])  # named, and not advanced
    assert np.abs(np.asarray(got[1, 4] - pool[1, 4])).max() > 0.01
    untouched = np.ones((3, 6), bool)
    untouched[1, 4] = False
    np.testing.assert_array_equal(np.asarray(got)[untouched], np.asarray(pool)[untouched])


def test_a_row_that_does_not_advance_reads_its_output_from_the_stored_state():
    """A step replayed at its position: the second call finds the state the
    first left, takes no update, leaves it bit for bit, and gives the first
    call's output, in the ``jax.numpy`` form and in the kernel."""
    q, k, v, g, beta = (x[:, 0] for x in inputs(1, seed=4))
    state = jnp.asarray(np.random.default_rng(2).normal(size=(B, DK, H * DV)), jnp.float32)
    yes, no = jnp.ones((B,), bool), jnp.zeros((B,), bool)
    first_o, after = G.gated_delta_step(state, q, k, v, g, beta, yes)
    again_o, still = G.gated_delta_step(after, q, k, v, g, beta, no)
    np.testing.assert_array_equal(first_o, again_o)
    np.testing.assert_array_equal(after, still)
    pool = jnp.zeros((1, 3, DK, H * DV)).at[0, 1:].set(state)
    rows = jnp.asarray([1, 2])
    first_o, pool = G.gated_delta_update(pool, 0, rows, yes, q, k, v, g, beta, interpret=True)
    kept = np.asarray(pool)
    again_o, pool = G.gated_delta_update(pool, 0, rows, no, q, k, v, g, beta, interpret=True)
    # the interpreter hands the body to XLA's CPU backend, which may compute the new state a second time inside
    # the output's sum and fuse its multiply-adds another way: a last place, where the chip's kernel has none
    np.testing.assert_allclose(first_o, again_o, atol=1e-6)
    np.testing.assert_array_equal(kept, pool)


def test_the_gates_and_where_the_kernel_is_chosen(monkeypatch):
    a, b = jnp.asarray([[0.3, -1.0]]), jnp.asarray([[0.0, 2.0]])
    a_log, dt_bias = jnp.log(jnp.asarray([1.0, 2.0])), jnp.asarray([-3.0, -4.0])
    g, beta = G.decay_and_strength(a, b, a_log, dt_bias, allow_neg_eigval=True)
    np.testing.assert_allclose(g, [[-np.log1p(np.exp(-2.7)), -2 * np.log1p(np.exp(-5.0))]], rtol=1e-6)
    np.testing.assert_allclose(beta, [[1.0, 2 / (1 + np.exp(-2.0))]], rtol=1e-6)
    assert float(G.decay_and_strength(a, b, a_log, dt_bias, allow_neg_eigval=False)[1][0, 0]) == 0.5
    # platform and static shape alone: never on the CPU; on a TPU, heads that pair up into whole lane tiles
    assert not G.can_use_gated_delta_kernel(30, 96, 192)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert G.can_use_gated_delta_kernel(30, 96, 192) and G.can_use_gated_delta_kernel(4, 8, 128)
    assert not G.can_use_gated_delta_kernel(3, 96, 192)  # an odd head has half a tile to itself
    assert not G.can_use_gated_delta_kernel(30, 90, 192)  # keys that do not fill sublane tiles


@pytest.mark.parametrize("activation", ["silu", None])
def test_the_short_convolutions_step_is_the_convolution_written_out(activation):
    """(``activation`` None: the taps' sum as it is, where the convolution is
    the whole mixer and gated outside, ``models/lfm2_moe.py``.) A window is the last K inputs, the current one among them: shifting a
    token in and summing the K taps a channel gives ``silu(sum_j w_j u_{t-K+1+j})``
    over the whole sequence's inputs, zeros before its start. The step runs over
    all of a layer's windows: a row nobody holds, and the null row that every
    inactive slot names, stay as they were; a row that does not advance reads
    the same output from the window as stored, whatever input it is handed."""
    taps, steps, channels, rows = 4, 7, 96, 5
    r = np.random.default_rng(1)
    u = jnp.asarray(r.normal(size=(3, steps, channels)), jnp.float32)  # slot 2 is inactive throughout
    w = jnp.asarray(r.normal(size=(taps, channels)), jnp.float32)
    padded = np.concatenate([np.zeros((3, taps - 1, channels)), np.asarray(u, np.float64)], axis=1)
    summed = sum(padded[:, j:j + steps] * np.asarray(w, np.float64)[j] for j in range(taps))
    want = summed / (1 + np.exp(-summed)) if activation else summed
    kw = {} if activation else {"activation": None}  # left out: the SiLU every recurrent kind's convolution has
    held = np.asarray([3, 1, 0])  # the rows the three slots name; the inactive one names the null row
    owner = jnp.asarray((held[None, :] == np.arange(rows)[:, None]) & np.asarray([True, True, False])[None, :])
    windows = jnp.zeros((rows, taps * channels), jnp.float32).at[4].set(7.0)
    for t in range(steps):
        got, windows = G.short_conv_step(windows, u[:, t], w, owner, jnp.any(owner, axis=1), **kw)
        np.testing.assert_allclose(got[:2], want[:2, t], atol=1e-5)
        again, still = G.short_conv_step(windows, u[:, t] * 0 + 9.0, w, owner, jnp.zeros((rows,), bool), **kw)  # replayed
        np.testing.assert_array_equal(again, got)
        np.testing.assert_array_equal(still, windows)
    assert not np.asarray(windows[0]).any() and not np.asarray(windows[2]).any() and (np.asarray(windows[4]) == 7.0).all()
    # a row's window is its last K inputs, the oldest first: what a prefill writes there
    np.testing.assert_array_equal(windows[3], u[0, -taps:].reshape(-1))
