"""The expert layer's window (``models/moe.py``): the grouped matmuls see the
held (token, choice) rows ``window_rows`` at a time and walk windows until
they run out. Against a plain loop over tokens and choices written here, for
the three routing rules, whatever the routing leaves the walk to do."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import moe

D, F = 16, 32
WINDOWS = moe.COUNTS.index("windows")
RULES = {"softmax": moe.route, "sigmoid": moe.route_sigmoid, "topk_softmax": moe.route_topk_softmax}


def pairs_visited(sizes, tile):
    """The (row tile, expert) pairs a grouped call visits, counted from the
    sorted groups: an expert with a row, every tile from its first row's to its
    last's."""
    ends = np.cumsum(sizes)
    return int(sum((e - 1) // tile - (e - n) // tile + 1 for e, n in zip(ends, sizes) if n))


def plain(params, u, *, n_routed, top_k, scale, rule, expert_offset=0, live=None, layer=None, rows=None):
    """(y, counts without ``windows``, ``pairs``): a token at a time, a choice
    at a time, the parts added in the order of the choices; ``pairs`` of the
    held experts' rows in expert order under the row tile the layer's static
    shapes give. Without ``e_gate`` an expert is ``e_down relu(e_up v)^2``;
    ``rows`` is what the experts read where it is not what the router reads."""
    take = (lambda w: w) if layer is None else (lambda w: w[layer])
    up, down = (np.asarray(take(params[k]), np.float32) for k in ("e_up", "e_down"))
    gate = np.asarray(take(params["e_gate"]), np.float32) if params.get("e_gate") is not None else up
    routed = rule(u, params["router"], params["router_bias"], top_k=top_k, scale=scale)
    weights, chosen = (np.asarray(a) for a in routed)
    rows = np.asarray(u if rows is None else rows, np.float32)
    y, sizes, zero, absent = np.zeros_like(rows), np.zeros(len(gate), np.int64), 0, 0
    for t in range(len(rows)):
        if live is not None and not live[t]:
            continue
        for w, e in zip(weights[t], chosen[t]):
            if e >= n_routed:
                zero, part = zero + 1, rows[t]
            elif 0 <= e - expert_offset < len(gate):
                e -= expert_offset
                sizes[e] += 1
                if gate is up:
                    part = np.square(np.maximum(rows[t] @ up[e], 0.0)) @ down[e]
                else:
                    part = (np.asarray(jax.nn.silu(rows[t] @ gate[e])) * (rows[t] @ up[e])) @ down[e]
            else:
                absent += 1
                continue
            y[t] += w * part
    n_rows, n_outputs = len(rows) * top_k, params["router"].shape[-1]
    tile = moe._row_tile(moe.window_rows(n_rows, len(gate), n_outputs), n_rows / n_outputs, up[0].size)
    return y, [int(sizes.sum()), zero, absent, int((sizes > 0).sum()), int(sizes.max())], pairs_visited(sizes, tile)


def layer_params(held, n_outputs, *, favoured=(), shunned=(), seed=0):
    """Seeded float32 weights; the choice bias sends every token to the
    ``favoured`` outputs and none to the ``shunned``."""
    params = moe.init_expert_params(jax.random.PRNGKey(seed), D, F, held=held, n_outputs=n_outputs)
    bias = np.zeros(n_outputs, np.float32)
    bias[list(favoured)], bias[list(shunned)] = 100.0, -100.0  # past any score of the three rules (a logit reaches 20)
    return {**params, "router_bias": params["router_bias"] + bias}


C = 8  # a latent half the width


def latent_experts(held, width, *, gated, seed=0):
    """Seeded float32 experts (held, width, F), (held, F, width): three
    matrices where ``gated``, else ``e_up`` and ``e_down`` alone."""
    k = jax.random.split(jax.random.PRNGKey(100 + seed), 3)
    made = {"e_gate": jax.random.normal(k[0], (held, width, F)) * width ** -0.5,
            "e_up": jax.random.normal(k[1], (held, width, F)) * width ** -0.5,
            "e_down": jax.random.normal(k[2], (held, F, width)) * F ** -0.5}
    return made if gated else {name: w for name, w in made.items() if name != "e_gate"}


# name: (tokens, held, routed, outputs, top_k, favoured, shunned, extra arguments (``latent``: two-matrix experts that
# read ``rows=`` in a latent of ``C``), windows walked)
CASES = {
    # 128 rows, 8 expected of an even router: a window of 32 and room to spare
    "one_window": (64, 4, 56, 64, 2, (), (), {}, 1),
    # every token's first choice is held expert 0: 40 held rows and a few more, past 32
    "two_windows": (40, 4, 56, 64, 2, (0,), (), {}, 2),
    # every token chooses held experts 0 and 1: all 144 rows held, in windows of 32
    "five_windows": (72, 4, 56, 64, 2, (0, 1), (), {}, 5),
    # every expert held: the window is every row and nothing is walked twice
    "every_expert_held": (24, 6, 6, 8, 3, (), (), {}, 1),
    # no token chooses a held expert: no window, and the identity experts' part alone
    "no_held_row": (48, 4, 56, 64, 2, (), (0, 1, 2, 3), {}, 0),
    # the chip's experts are 8..11 of the 56; two windows of them
    "offset_share": (40, 4, 56, 64, 2, (9,), (), {"expert_offset": 8}, 2),
    # rows that are no tokens route nowhere: 25 live rows of 50 fit one window
    "masked_rows": (50, 4, 56, 64, 2, (0,), (), {"live": np.arange(50) % 2 == 0}, 1),
    "masked_rows_spill": (90, 4, 56, 64, 2, (0,), (), {"live": np.arange(90) % 2 == 0}, 2),
    # the experts of three layers stacked, the second one's used
    "stacked_layer": (40, 4, 56, 64, 2, (2,), (), {"layer": 1}, 2),
    # Granite's: half the outputs held, ten choices a token, no identity expert; 480 rows, past the ridge and no whole
    # row tiles, are one window of 512 whose results are summed from where the grouped matmul left them
    "granite_like": (48, 36, 72, 72, 10, (), (), {}, 1),
    # Nemotron's: 22 choices a token, a quarter of the outputs held, experts of two matrices in a latent (``rows=``);
    # fifteen of the sixteen held experts in every token's choice: 1,584 rows walked in three windows of 512
    "nemotron_like": (72, 16, 64, 64, 22, tuple(range(15)), (), {"latent": True}, 3),
}


def case_inputs(case):
    """(params, tokens, the layer's extra arguments) of a case: its choice
    bias, its stack of three layers, its latent rows and two-matrix experts."""
    t, held, _, n_outputs, _, favoured, shunned, extra, _ = CASES[case]
    params, extra = layer_params(held, n_outputs, favoured=favoured, shunned=shunned), dict(extra)
    if "layer" in extra:
        others = [layer_params(held, n_outputs, seed=s) for s in (1, 2)]
        for k in ("e_gate", "e_up", "e_down"):
            params[k] = jnp.stack([others[0][k], params[k], others[1][k]])
    u = jax.random.normal(jax.random.PRNGKey(7), (t, D))
    if extra.pop("latent", False):
        params = {**{k: w for k, w in params.items() if not k.startswith("e_")}, **latent_experts(held, C, gated=False)}
        extra["rows"] = u @ (jax.random.normal(jax.random.PRNGKey(8), (D, C)) * D ** -0.5)
    return params, u, extra


@pytest.mark.parametrize("rule", list(RULES))
@pytest.mark.parametrize("case", list(CASES))
def test_the_walk_agrees_with_a_plain_loop(case, rule):
    t, held, n_routed, n_outputs, top_k, favoured, shunned, _, windows = CASES[case]
    params, u, extra = case_inputs(case)
    kw = dict(n_routed=n_routed, top_k=top_k, scale=2.5, rule=RULES[rule], **extra)
    y, counts = jax.jit(lambda rows: moe.expert_layer(params, rows, **kw))(u)
    want, want_counts, pairs = plain(params, u, **kw)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    assert dict(zip(moe.COUNTS, np.asarray(counts).tolist())) == dict(zip(moe.COUNTS, want_counts + [windows, pairs]))
    n_held, window = want_counts[0], moe.window_rows(t * top_k, held, n_outputs)
    assert windows == -(-n_held // window)  # the case is what its name says
    if case == "no_held_row":
        w, chosen = RULES[rule](u, params["router"], params["router_bias"], top_k=top_k, scale=2.5)
        share = np.sum(np.where(np.asarray(chosen) >= n_routed, np.asarray(w), 0.0), -1, keepdims=True)
        identity = share * np.asarray(u)
        assert want_counts[1] > 0
        np.testing.assert_allclose(np.asarray(y), identity, atol=1e-6)


@pytest.mark.parametrize("rule", list(RULES))
def test_a_tokens_result_is_the_same_bit_for_bit_wherever_its_rows_fall(rule):
    """Alone (its ``top_k`` rows are the window), among 19 others (one window
    of 32), and first or last of 40 tokens that all choose held expert 0, so
    that its row for that expert is the first window's first or the second
    window's eighth."""
    params = layer_params(4, 64, favoured=(0,))
    kw = dict(n_routed=56, top_k=2, scale=2.5, rule=RULES[rule])
    layer = jax.jit(lambda rows: moe.expert_layer(params, rows, **kw))
    token = jax.random.normal(jax.random.PRNGKey(11), (1, D))
    others = jax.random.normal(jax.random.PRNGKey(12), (39, D))
    alone, counts = layer(token)
    assert np.asarray(counts).tolist()[0] >= 1 and np.any(np.asarray(alone) != 0)
    among, counts = layer(jnp.concatenate([others[:7], token, others[7:19]]))
    assert np.asarray(counts).tolist()[WINDOWS] == 1
    first, counts_first = layer(jnp.concatenate([token, others]))
    last, counts_last = layer(jnp.concatenate([others, token]))
    assert np.asarray(counts_first).tolist() == np.asarray(counts_last).tolist() and np.asarray(counts_last)[WINDOWS] == 2
    for got in (among[7], first[0], last[-1]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(alone[0]))


@pytest.mark.parametrize("rule", list(RULES))
def test_at_ten_choices_a_token_the_result_is_the_same_bits_alone_and_across_two_windows(rule):
    """The same at a ``top_k`` that is no multiple of 8 (Granite's ten). Alone
    its ten rows are the window; among 19 others the 60 held rows and a few
    more walk three windows of 32; first or last of 60 tokens that all choose held experts 0, 1 and 2, 180
    held rows and a few more in two windows of 128, its row for expert 2 is the
    first window's 121st or the second's 52nd. The tokens and the router are
    in eighths, so that a logit is exact in whatever order its sixteen products
    add: the CPU multiplies one row by another routine than sixty, and of ten
    chosen weights one then differs in its last bit before the layer has begun."""
    eighths = lambda x: jnp.round(x * 8) / 8
    params = layer_params(4, 64, favoured=(0, 1, 2))
    params["router"] = eighths(params["router"])
    kw = dict(n_routed=56, top_k=10, scale=2.5, rule=RULES[rule])
    layer = jax.jit(lambda rows: moe.expert_layer(params, rows, **kw))
    token = eighths(jax.random.normal(jax.random.PRNGKey(11), (1, D)))
    others = eighths(jax.random.normal(jax.random.PRNGKey(12), (59, D)))
    alone, counts = layer(token)
    assert np.asarray(counts).tolist()[0] >= 3 and np.asarray(counts).tolist()[WINDOWS] == 1 and np.any(np.asarray(alone) != 0)
    among, counts = layer(jnp.concatenate([others[:7], token, others[7:19]]))
    assert np.asarray(counts).tolist()[WINDOWS] == 3
    first, counts_first = layer(jnp.concatenate([token, others]))
    last, counts_last = layer(jnp.concatenate([others, token]))
    assert np.asarray(counts_first).tolist() == np.asarray(counts_last).tolist() and np.asarray(counts_last)[WINDOWS] == 2
    assert moe.window_rows(600, 4, 64) == 128 and 180 <= np.asarray(counts_last)[0] <= 256
    for got in (among[7], first[0], last[-1]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(alone[0]))


def _values(jaxpr):
    """Every (equation, value it makes or reads) of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        for var in (*eqn.invars, *eqn.outvars):
            yield eqn, var
        for param in eqn.params.values():
            for inner in (param if isinstance(param, (tuple, list)) else (param,)):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _values(inner)


@pytest.mark.parametrize("case", ["granite_like", "nemotron_like"])
def test_no_buffer_of_every_place_is_made_scattered_into_or_laid_anew(case):
    """The traced layer at one window of every row and at several windows
    walked: no value of it is (tokens, choices, width) and no scatter writes a
    float32 (tokens x choices, width) operand. A token's result rows are read
    from the sorted order the grouped matmuls wrote them in (PR 58): the buffer
    of every (token, choice) place, zeroed, scattered into and re-laid before
    the sum, cannot come back unnoticed."""
    t, held, n_routed, _, top_k, *_ = CASES[case]
    params, u, extra = case_inputs(case)
    width = extra["rows"].shape[1] if "rows" in extra else D
    traced = jax.make_jaxpr(lambda a, b: moe.expert_layer(params, a, rows=b, n_routed=n_routed, top_k=top_k, scale=1.0))(u, extra.get("rows"))
    seen = [(eqn.primitive.name, tuple(var.aval.shape), str(var.aval.dtype)) for eqn, var in _values(traced.jaxpr)
            if hasattr(var, "aval") and hasattr(var.aval, "shape")]
    assert len(seen) > 100 and any(name.startswith("ragged_dot") for name, _, _ in seen)
    # however the width is laid
    assert not [s for s in seen if s[1][:2] == (t, top_k) and np.prod(s[1]) == t * top_k * width]
    scatters = [eqn for eqn, _ in _values(traced.jaxpr) if eqn.primitive.name.startswith("scatter")]
    operands = {tuple(eqn.invars[0].aval.shape) for eqn in scatters}
    assert not [shape for shape in operands if np.prod(shape) >= t * top_k * width]
    assert operands == {(held + 1,)}  # the bincount's alone


@pytest.mark.parametrize("rule", list(RULES))
@pytest.mark.parametrize("case", ["granite_like", "nemotron_like", "two_windows", "no_held_row", "masked_rows_spill"])
def test_a_prompts_walk_of_the_held_choices_gives_the_decode_steps_sum(monkeypatch, case, rule):
    """``moe.combine`` past ``_GATHERED_BYTES`` (here: always) walks every
    token's j-th held choice in trip j, as many trips as the token with the
    most held choices has; under it, one gather of every row. The same parts in the
    same order: against the plain loop, the counts, and the other walk's bits
    (the rule's weights rounded to powers of two: a weight times a row is then
    exact, and the bits do not depend on whether the CPU's compiler fuses a
    product into the add that follows it, as it does in one walk and not the
    other)."""
    def powers_of_two(*args, **kwargs):
        weights, experts = RULES[rule](*args, **kwargs)
        return 2.0 ** jnp.round(jnp.log2(weights)), experts

    _, _, n_routed, _, top_k, *_, windows = CASES[case]
    params, u, extra = case_inputs(case)
    kw = dict(n_routed=n_routed, top_k=top_k, scale=2.5, rule=powers_of_two, **extra)
    one_gather, _ = jax.jit(lambda rows: moe.expert_layer(params, rows, **kw))(u)
    monkeypatch.setattr(moe, "_GATHERED_BYTES", 0)
    traced = str(jax.make_jaxpr(lambda rows: moe.expert_layer(params, rows, **kw))(u))
    assert traced.count("gather[") == 3 and "while[" in traced  # the loop's one, the window's rows', the router's
    y, counts = jax.jit(lambda rows: moe.expert_layer(params, rows, **kw))(u)
    want, want_counts, pairs = plain(params, u, **kw)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    assert np.asarray(counts).tolist() == want_counts + [windows, pairs]
    np.testing.assert_array_equal(np.asarray(y), np.asarray(one_gather))


@pytest.mark.parametrize("latent,gated", [(False, False), (True, False), (True, True)],
                         ids=["two_matrices", "two_matrices_latent_rows", "gated_latent_rows"])
@pytest.mark.parametrize("case", ["two_windows", "every_expert_held", "offset_share", "masked_rows_spill", "stacked_layer", "no_held_row"])
def test_two_matrix_experts_and_latent_rows_agree_with_a_plain_loop(case, latent, gated):
    """What the caller hands in chooses the expert: without ``e_gate`` two
    grouped calls a window, ``e_down relu(e_up v)^2``; with ``rows`` the experts
    read and write the latent (held, C, F), (held, F, C) while the router reads
    ``u``, the identity experts add ``w * v`` and the result is latent-wide.
    Against the loop over tokens and choices, under the sigmoid rule, through
    one window, several, none, a share from an offset, masked rows and a stack
    of layers (the gated expert over the residual width is
    ``test_the_walk_agrees_with_a_plain_loop``'s)."""
    t, held, n_routed, n_outputs, top_k, favoured, shunned, extra, windows = CASES[case]
    width = C if latent else D

    def experts(seed):
        return latent_experts(held, width, gated=gated, seed=seed)

    params = {**{k: v for k, v in layer_params(held, n_outputs, favoured=favoured, shunned=shunned).items() if not k.startswith("e_")},
              **experts(0)}
    if "layer" in extra:
        others = [experts(1), experts(2)]
        for k in experts(0):
            params[k] = jnp.stack([others[0][k], params[k], others[1][k]])
    u = jax.random.normal(jax.random.PRNGKey(7), (t, D))
    v = u @ (jax.random.normal(jax.random.PRNGKey(8), (D, C)) * D ** -0.5) if latent else None
    kw = dict(n_routed=n_routed, top_k=top_k, scale=2.5, rule=moe.route_sigmoid, **extra)
    y, counts = jax.jit(lambda a, b: moe.expert_layer(params, a, rows=b, **kw))(u, v)
    want, want_counts, pairs = plain(params, u, rows=v, **kw)
    assert y.shape == (t, width)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    assert dict(zip(moe.COUNTS, np.asarray(counts).tolist())) == dict(zip(moe.COUNTS, want_counts + [windows, pairs]))
    if case == "no_held_row":  # the identity experts' part alone: the weights times what the experts would have read
        assert want_counts[1] > 0 and np.abs(want).max() > 1e-3


def test_the_third_rule_by_hand_a_softmax_over_the_chosen_logits():
    """Three tokens of one value through a "router" whose rows are their
    logits: the ``top_k`` largest are chosen and the weights are their softmax,
    which is the softmax over every output renormalised over the chosen; a
    bias moves the choice and never the weights; ``scale`` multiplies them."""
    logits = jnp.asarray([[2.0, 0.0, 1.0, -1.0, 3.0], [0.0, 0.0, 5.0, 4.0, -2.0], [1.0, 2.0, 3.0, 4.0, 5.0]])
    router = jnp.stack([logits[i] for i in range(3)])  # token i is e_i: its logits are row i
    w, e = moe.route_topk_softmax(jnp.eye(3), router, None, top_k=2, scale=1.0)
    assert np.asarray(e).tolist() == [[4, 0], [2, 3], [4, 3]]
    by_hand = np.asarray([[np.e, 1.0], [np.e, 1.0], [np.e, 1.0]]) / (np.e + 1.0)  # each pair a logit apart
    np.testing.assert_allclose(np.asarray(w), by_hand, rtol=1e-6)
    full = np.asarray(jax.nn.softmax(logits, axis=-1))
    picked = np.take_along_axis(full, np.asarray(e), axis=-1)
    np.testing.assert_allclose(np.asarray(w), picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    assert not np.allclose(np.asarray(w), picked, rtol=1e-2)  # and not the softmax over every output
    bias = jnp.asarray([0.0, 10.0, 0.0, 0.0, 0.0])  # output 1 into every token's choice
    wb, eb = moe.route_topk_softmax(jnp.eye(3), router, bias, top_k=2, scale=2.0)
    assert (np.asarray(eb)[:, 0] == 1).all() and np.asarray(eb)[:, 1].tolist() == [4, 2, 4]
    pairs = np.take_along_axis(np.asarray(logits), np.asarray(eb), axis=-1)  # the logits, unbiased
    np.testing.assert_allclose(np.asarray(wb), 2.0 * np.exp(pairs) / np.exp(pairs).sum(-1, keepdims=True), rtol=1e-6)


@pytest.mark.parametrize("n_rows,held,n_outputs,window", [
    (384, 12, 384, 32), (512, 12, 384, 32),  # Kimi-K2's decode step at 48 and at 64 slots
    (1024, 12, 384, 64), (2048, 12, 384, 128), (4096, 12, 384, 256),  # its prefill buckets
    (384, 16, 768, 32), (3072, 16, 768, 128), (6144, 16, 768, 256), (12288, 16, 768, 512),  # LongCat's
    (96, 8, 12, 96), (32, 2, 2, 32), (8, 4, 64, 8), (96, 6, 12, 96),  # most experts held, few rows: one window of every row
    (192, 64, 64, 192), (512, 64, 64, 512),  # LFM2's decode step at 48 and at 128 slots, every expert held: one window, as it was
    (1024, 64, 64, 1024), (2048, 64, 64, 2048), (4096, 64, 64, 4096), (8192, 64, 64, 8192),  # its prefill buckets: one call of every row
    # Granite's decode step at 48 slots: every row (twice the 240 an even router sends), past the ridge and no whole
    # tiles, so rounded up to 512 (four tiles of 128 since PR 60); at 32 slots 320 rows likewise; its prefill buckets
    # are whole tiles as they are
    (480, 36, 72, 512), (320, 36, 72, 512), (2560, 36, 72, 2560), (5120, 36, 72, 5120), (10240, 36, 72, 10240),
    (240, 36, 72, 240), (80, 36, 72, 80),  # under the ridge: every row, one tile
    # Nemotron 3 Super's: 22 choices over 512 outputs, 128 held. A decode step's 1,056 rows (264 held of an even router)
    # are a window of 512 (row tiles of 128 since PR 60: the held rows fill three); its prefill buckets walk windows of 512
    (1056, 128, 512, 512), (704, 128, 512, 512), (5632, 128, 512, 512), (11264, 128, 512, 512), (22528, 128, 512, 512),
])
def test_the_window_follows_the_held_rows_and_not_the_batch(n_rows, held, n_outputs, window):
    assert moe.window_rows(n_rows, held, n_outputs) == window


@pytest.mark.parametrize("rows,k,n,dtype,taken", [
    (32, 7168, 2048, jnp.bfloat16, True), (32, 2048, 7168, jnp.bfloat16, True),  # Kimi-K2's decode window: gate, down
    (512, 6144, 2048, jnp.bfloat16, True), (512, 2048, 6144, jnp.bfloat16, True),  # LongCat's 1,024 bucket
    (1024, 7168, 2048, jnp.bfloat16, True),  # past 512 rows in whole row tiles: every expert held, a prefill (PR 50)
    (640, 7168, 2048, jnp.bfloat16, False),  # past 512 rows and no whole row tiles
    (512, 2048, 1536, jnp.bfloat16, True), (512, 1536, 2048, jnp.bfloat16, True),  # LFM2's expert of 2048 x 1536: gate, down
    (512, 1024, 2688, jnp.bfloat16, True), (512, 2688, 1024, jnp.bfloat16, True),  # Nemotron 3 Super's, in its latent: up, down
    (24, 7168, 2048, jnp.bfloat16, False),  # no whole sublane tiles
    (32, 7168, 2048, jnp.float32, False),  # the tests' float32 twins
    (32, 7000, 2048, jnp.bfloat16, False), (32, 2048, 1000, jnp.bfloat16, False),  # no whole weight tiles
    (512, 128, 65536, jnp.bfloat16, False),  # whole tiles of whole lanes, and more fast memory than a kernel may ask for
])
def test_the_grouped_kernel_is_chosen_by_platform_and_static_shape(monkeypatch, rows, k, n, dtype, taken):
    x, w = jax.ShapeDtypeStruct((rows, k), dtype), jax.ShapeDtypeStruct((6, k, n), dtype)
    assert not moe.can_use_grouped_kernel(x, w)  # the CPU keeps ragged_dot
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe.can_use_grouped_kernel(x, w) == taken
    tk, tn = moe._weight_tile(k, n, moe._row_tile(rows))
    assert tk * tn <= moe._WEIGHT_TILE and (not taken or (k % tk == 0 and n % tn == 0))


def _stacked_operands(rows, sizes, k=4096, n=256):
    """``rows`` rows, three layers' experts stacked, and groups of which the
    second layer's alone have rows (``sizes``). ``k`` past ``_WHOLE_K``: tiles
    of 2,048 x 256, two steps of the contraction."""
    held = len(sizes)
    x = jax.random.normal(jax.random.PRNGKey(0), (rows, k)).astype(jnp.bfloat16)
    w = (jax.random.normal(jax.random.PRNGKey(1), (3 * held, k, n)) * k ** -0.5).astype(jnp.bfloat16)
    return x, w, jnp.zeros((3 * held,), jnp.int32).at[held:2 * held].set(jnp.asarray(sizes))


# rows handed in, the rows of one layer's groups, the tiling ``grouped_matmul`` states, (k, n) where not 4,096 x 256
TILINGS = {
    # a decode step's window: one row tile, dead rows past the last group
    "decode_window": (32, [5, 0, 9, 7], (32, 2048, 256)),
    # a prefill's window, two row tiles: the third group's rows straddle row 256, 62 dead rows at the end
    "straddles_row_256": (512, [100, 0, 200, 150], (256, 2048, 256)),
    # held rows end inside the first tile: the second is wholly dead (LongCat's 1,024 bucket) and never visited
    "second_tile_dead": (512, [60, 0, 90, 70], (256, 2048, 256)),
    # a group ends at the boundary, no row is dead
    "boundary_and_full": (512, [256, 0, 128, 128], (256, 2048, 256)),
    # rows that are no whole tiles stay one tile
    "one_odd_tile": (144, [40, 0, 60, 30], (144, 2048, 256)),
    # LFM2's expert, gate and down, whole: a decode step's 192 rows, empty groups, a ragged last group, dead rows
    "lfm2_gate_whole": (192, [3, 0, 5, 1, 0, 7, 2, 13], (192, 2048, 1536), (2048, 1536)),
    "lfm2_down_whole": (192, [3, 0, 5, 1, 0, 7, 2, 13], (192, 1536, 2048), (1536, 2048)),
    # K-EXAONE's and LongCat's: a decode step's window in twelve tiles of 2 MB, along the contraction (gate) and the
    # width (down); a prefill's, under row tiles of 256, in three of 8 MB
    "exaone_gate": (128, [9, 0, 30], (128, 512, 2048), (6144, 2048)),
    "exaone_down": (128, [9, 0, 30], (128, 2048, 512), (2048, 6144)),
    "exaone_gate_prefill": (512, [200, 0, 230], (256, 2048, 2048), (6144, 2048)),
    "exaone_down_prefill": (512, [200, 0, 230], (256, 2048, 2048), (2048, 6144)),
    # Nemotron 3 Super's two matrices in its latent, a window of 512 under row tiles of 256 whose held rows pass the first
    # by a few: up whole (5.5 MB), down in three tiles of its contraction of 2,688
    "nemotron_up": (512, [120, 0, 100, 44], (256, 1024, 2688), (1024, 2688)),
    "nemotron_down": (512, [120, 0, 100, 44], (256, 896, 1024), (2688, 1024)),
    # a decode step's window of 512 under the row tiles of 128 ``expert_layer`` hands down where an expert gets a few rows
    # (PR 60): Granite's gate in two tiles of its contraction, Nemotron's down in three; groups across rows 128 and 256
    "granite_gate_step": (512, [70, 0, 90, 60, 20], (128, 2048, 768), (4096, 768)),
    "nemotron_down_step": (512, [120, 0, 100, 44], (128, 896, 1024), (2688, 1024)),
}


@pytest.mark.parametrize("case", list(TILINGS))
def test_the_grouped_kernel_at_its_tiling_agrees_with_ragged_dot(monkeypatch, case):
    """The grouped kernel (``ops/grouped_matmul.py``) in interpret mode, at the
    tiling ``grouped_matmul`` states, against ``jax.lax.ragged_dot``: groups
    of a stacked tensor of which one layer's have rows, an empty group among
    them, and dead rows past the last group (left as whatever was there)."""
    from ray_tpu.ops.grouped_matmul import gmm

    rows, sizes, tiling, *shape = TILINGS[case]
    x, w, groups = _stacked_operands(rows, sizes, *(shape[0] if shape else ()))
    want = jax.lax.ragged_dot(x, w, groups, preferred_element_type=jnp.float32)
    stated = []

    def interpreted(*args, tiling, **kwargs):
        stated.append(tiling)
        return gmm(*args, tiling=tiling, interpret=True, **kwargs)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the branch ``grouped_matmul`` takes on the chip
    monkeypatch.setattr(moe, "_gmm", interpreted)
    # a row tile that is not what the operand's rows alone give is the caller's to state, as ``expert_layer`` does
    got = moe.grouped_matmul(x, w, groups, jnp.float32, None if tiling[0] == moe._row_tile(rows) else tiling[0])
    assert stated == [tiling]
    live = sum(sizes)
    np.testing.assert_allclose(np.asarray(got)[:live], np.asarray(want)[:live], rtol=2e-2, atol=2e-2)
    np.testing.assert_array_equal(np.asarray(want)[:live] != 0, True)


# rows of one layer's groups in a window of 512, the contraction and the width, the row tiles compared
BITS = {
    **{c: (sizes, 4096, 256, (moe.ROW_TILE, 512)) for c, (rows, sizes, *shape) in TILINGS.items() if rows == 512 and len(shape) == 1},
    # Granite's decode step: 36 groups of ~7 rows, 252 held; the nineteenth's rows straddle row 128
    "granite_step_128": ([7] * 36, 512, 256, (128, moe.ROW_TILE)),
    # Nemotron's: 128 groups of 0-3 rows, 268 held in three tiles of 128, an expert's rows across each boundary
    "nemotron_step_128": ([(3, 2, 0, 3, 2, 3, 2, 3)[i % 8] for i in range(120)] + [2] * 8, 512, 256, (128, moe.ROW_TILE)),
}


@pytest.mark.parametrize("case", list(BITS))
def test_a_rows_result_is_the_same_bits_under_either_row_tile(case):
    """The contraction's order is the weight tile's: the held rows of a window
    come out bit for bit the same through row tiles of 256 and through the one
    tile of 512 that a prefill's window was; and through tiles of 128, the
    decode steps' of Granite and Nemotron (PR 60), where an expert's rows lie
    across a boundary that tiles of 256 do not have."""
    from ray_tpu.ops.grouped_matmul import gmm

    sizes, k, n, tiles = BITS[case]
    x, w, groups = _stacked_operands(512, sizes, k, n)
    if tiles[0] == 128:
        assert pairs_visited(sizes, 128) > pairs_visited(sizes, 256) >= sum(g > 0 for g in sizes)  # one straddles, at the least
    first, second = (np.asarray(gmm(x, w, groups, preferred_element_type=jnp.float32, tiling=(tm, k // 2, n), interpret=True))
                     for tm in tiles)
    live = sum(sizes)
    assert live <= 512 and np.all(first[:live] != 0)
    np.testing.assert_array_equal(first[:live].view(np.uint32), second[:live].view(np.uint32))


def test_a_window_never_exceeds_the_kernels_row_tile_and_the_older_kinds_windows_are_what_they_were():
    """``window_rows`` at the shapes the benchmark's kinds reach. Kimi-K2 (12 of
    384, top-8) and LongCat (16 of 768 outputs, top-12) never met the cap: their
    largest windows are 512 exactly, and their programs lower as before.
    K-EXAONE holds an eighth of its experts: its 512 and 1,024 buckets would
    have had windows of 1,024 and 2,048 rows, past the rows the grouped kernel
    takes in one call, and walk windows of 512 instead. A layer that holds
    every expert (LFM2's 64) has one window of every row, a decode step's 512
    and a prefill's 4,096 or 8,192, which the grouped kernel takes under row
    tiles of 256 (past 512 rows they went through ``ragged_dot`` until PR 50)."""
    kimi = {48: 32, 128: 64, 256: 128, 512: 256, 1024: 512}
    for tokens, window in kimi.items():
        assert moe.window_rows(tokens * 8, 12, 384) == window
    longcat = {32: 32, 256: 128, 512: 256, 1024: 512}
    for tokens, window in longcat.items():
        assert moe.window_rows(tokens * 12, 16, 768) == window
    exaone = {48: 128, 256: 512, 512: 512, 1024: 512}
    for tokens, window in exaone.items():
        assert moe.window_rows(tokens * 8, 16, 128) == window <= moe._KERNEL_ROWS
    assert moe.window_rows(48 * 3, 4, 8) == 144
    for rows, window in {192: 192, 512: 512, 4096: 4096, 8192: 8192}.items():
        assert moe.window_rows(rows, 64, 64) == window
        x, w = jax.ShapeDtypeStruct((window, 2048), jnp.bfloat16), jax.ShapeDtypeStruct((64, 2048, 1536), jnp.bfloat16)
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(jax, "default_backend", lambda: "tpu")
            assert moe.can_use_grouped_kernel(x, w)


# (k, n) of every expert matrix the four expert configurations multiply by (gate and up; down) -> its weight tile
# under a row tile below the ridge (a decode step's window, the small buckets') and under one at it (``ROW_TILE``)
WEIGHT_TILES = {
    "longcat": {(6144, 2048): ((512, 2048), (2048, 2048)), (2048, 6144): ((2048, 512), (2048, 2048))},
    "kimi": {(7168, 2048): ((512, 2048), (1792, 2048)), (2048, 7168): ((2048, 512), (2048, 1792))},
    "exaone": {(6144, 2048): ((512, 2048), (2048, 2048)), (2048, 6144): ((2048, 512), (2048, 2048))},
    "lfm2": {(2048, 1536): ((2048, 1536), (2048, 1536)), (1536, 2048): ((1536, 2048), (1536, 2048))},
    "granite": {(4096, 768): ((2048, 768), (2048, 768)), (768, 4096): ((768, 4096), (768, 4096))},
    "nemotron": {(1024, 2688): ((1024, 2688), (1024, 2688)), (2688, 1024): ((896, 1024), (896, 1024))},
}


@pytest.mark.parametrize("kind,k,n", [(kind, k, n) for kind, tiles in WEIGHT_TILES.items() for k, n in tiles])
def test_every_expert_matrix_gets_a_tile_of_whole_lanes_that_divides_it_and_the_older_kinds_theirs_as_before(kind, k, n):
    """Under a row tile at the ridge, where the products bound a call, the
    fewest tiles of at most 8 MB, of whole lanes, that divide the matrix: the
    three older kinds' 25 and 29 MB in three and four tiles, where they went by
    in twelve and fourteen of 2 MB (PR 38) and under a smaller row tile still
    do, the tile they got. LFM2's two shapes fit a tile and go by whole under
    any row tile (its ``e_down`` once fell to ``ragged_dot`` on 682 columns,
    then went by in four tiles of 512: runs of 1 KB at a stride of 4 KB), as do
    Granite's 6.3 MB: ``e_down`` whole, ``e_gate`` in two halves of its
    contraction of 4,096, and Nemotron 3 Super's 5.5 MB: ``e_up`` whole,
    ``e_down`` in three tiles of its contraction of 2,688. The contraction is cut only where it is past
    ``_WHOLE_K``. At every row tile a
    window reaches, the call's buffers fit the fast memory it states."""
    from ray_tpu.ops.grouped_matmul import VMEM_BUDGET, vmem_bytes

    for rows, want in zip((32, 128, 192, moe.ROW_TILE), 3 * WEIGHT_TILES[kind][(k, n)][:1] + WEIGHT_TILES[kind][(k, n)][1:]):
        tk, tn = moe._weight_tile(k, n, rows)
        assert (tk, tn) == want
        assert tk % 128 == 0 and tn % 128 == 0 and n % tn == 0 and k % tk == 0
        fits = k * n <= moe._WEIGHT_TILE  # LFM2's, Granite's and Nemotron's: a matrix that fits a tile gets the larger tile at any row tile
        assert fits == (kind in ("lfm2", "granite", "nemotron"))
        assert tk * tn <= (moe._WEIGHT_TILE if rows == moe.ROW_TILE or fits else 1 << 20)
        assert (k <= moe._WHOLE_K) == (tk == k) and ((tk, tn) == (k, n)) == (fits and k <= moe._WHOLE_K)
        assert 2 * tk * tn * 2 < vmem_bytes((rows, tk, tn), 2, 4) <= 32 << 20 < VMEM_BUDGET


# kind: (choices a token, experts held, the router's outputs, {tokens: (window, row tile)})
REACHED = {
    "kimi": (8, 12, 384, {48: (32, 32), 128: (64, 64), 256: (128, 128), 512: (256, 256)}),
    "longcat": (12, 16, 768, {32: (32, 32), 256: (128, 128), 512: (256, 256), 1024: (512, 256)}),
    "exaone": (8, 16, 128, {48: (128, 128), 256: (512, 256), 512: (512, 256), 1024: (512, 256)}),
    "every_expert_held": (3, 4, 8, {48: (144, 144)}),
    "lfm2": (4, 64, 64, {48: (192, 192), 128: (512, 128), 256: (1024, 128), 512: (2048, 256), 1024: (4096, 256)}),
    "granite": (10, 36, 72, {48: (512, 128), 256: (2560, 256), 512: (5120, 256), 1024: (10240, 256)}),
    "nemotron": (22, 128, 512, {48: (512, 128), 256: (512, 128), 512: (512, 128), 1024: (512, 256)}),
}
EVERY_REACHED = [(kind, tokens) for kind, case in REACHED.items() for tokens in case[3]]


def _an_experts_matrix(kind):
    """(k, n) of the kind's gate and up (the test's own kind: a matrix that fits a tile)."""
    return next(iter(WEIGHT_TILES.get(kind, {(128, 128): None})))


@pytest.mark.parametrize("kind,tokens", EVERY_REACHED)
def test_the_row_tile_at_every_window_the_benchmarks_kinds_reach(monkeypatch, kind, tokens):
    """What ``grouped_matmul`` states for the window ``window_rows`` gives at
    a decode step's and every prefill bucket's tokens, under the row tile
    ``expert_layer`` hands it: one tile of the window's rows up to ``ROW_TILE``
    (every decode program but two, all of Kimi-K2, LongCat's smaller buckets:
    as they were), tiles of ``ROW_TILE`` in a window of 512 (LongCat's 1,024
    bucket, K-EXAONE's three) and in a whole layer's one window of every row
    (LFM2's, Granite's prefills), tiles of half that where an expert gets a
    few rows (Granite's and Nemotron's decode steps, Nemotron's 256 and 512
    buckets, LFM2's 256 bucket and its decode step at 128 slots: PR 60), and
    one tile where the rows are no whole tiles and under the ridge."""
    top_k, held, n_outputs, reached = REACHED[kind]
    window, row_tile = reached[tokens]
    assert moe.window_rows(tokens * top_k, held, n_outputs) == window
    k, n = _an_experts_matrix(kind)
    stated = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(moe, "_gmm", lambda x, w, g, *, tiling, preferred_element_type: stated.append(tiling) or x)
    moe.grouped_matmul(jnp.zeros((window, 128), jnp.bfloat16), jnp.zeros((held, 128, 128), jnp.bfloat16), jnp.zeros((held,), jnp.int32),
                       row_tile=moe._row_tile(window, tokens * top_k / n_outputs, k * n))
    assert stated == [(row_tile, 128, 128)] and window % row_tile == 0


@pytest.mark.parametrize("kind,tokens", EVERY_REACHED)
def test_the_rule_picks_the_stated_row_tile_from_static_shapes_alone(kind, tokens):
    """``moe._row_tile`` from the window's rows, the rows a call sends an
    expert in the mean and the elements of an expert's matrix: ``ROW_TILE``
    wherever an expert owns ``_SPARSE_ROWS`` rows or more of a call (LFM2's 512
    bucket 32, Granite's 256 bucket 36, Nemotron's 1,024 bucket 44, every
    larger one) or its matrix goes by in several weight tiles (the three older
    kinds', whose tiles are smaller under a smaller row tile: 10.7-64 rows an
    expert in their prefills), half of it under that (Nemotron's decode step
    2.06 rows an expert, Granite's 6.7, LFM2's at 128 slots 8; Nemotron's 256
    and 512 buckets 11 and 22, LFM2's 256 bucket 16), and whatever the rows
    are where they are no whole tiles. No model's name, no configuration key:
    two calls of one shape get one tile."""
    top_k, held, n_outputs, reached = REACHED[kind]
    window, row_tile = reached[tokens]
    an_expert, (k, n) = tokens * top_k / n_outputs, _an_experts_matrix(kind)
    assert moe._row_tile(window, an_expert, k * n) == row_tile
    if window % moe.ROW_TILE == 0:
        fits = k * n <= moe._WEIGHT_TILE
        assert (row_tile == moe.ROW_TILE // 2) == (an_expert < moe._SPARSE_ROWS and fits)
        assert moe._row_tile(window) == moe.ROW_TILE  # a caller that says nothing of its experts' rows gets the ridge
        assert moe._row_tile(window, moe._SPARSE_ROWS, k * n) == moe.ROW_TILE
        assert moe._row_tile(window, moe._SPARSE_ROWS - 0.01, k * n) == (moe.ROW_TILE // 2 if fits else moe.ROW_TILE)
    else:
        assert row_tile == window < moe.ROW_TILE


def test_an_expert_whose_rows_straddle_two_row_tiles_is_visited_twice():
    """48 experts, all held, three a token by a router that sends token type
    ``j`` (a unit vector) to experts ``3j .. 3j + 2``; 96 rows of which 80 are
    tokens, five of each type: every expert gets five rows, 240 held rows in a
    window of 512 under row tiles of 128 (six rows an expert by the static
    shapes). Sorted by expert, expert 25's rows are 125-129: it alone lies
    across row 128 and is visited in both tiles, ``pairs == touched + 1``;
    under one tile of 256 every touched expert was streamed once."""
    types, top_k, held = 16, 3, 48
    router = np.zeros((D, held), np.float32)
    for j in range(types):
        router[j, 3 * j:3 * j + 3] = (12.0, 11.0, 10.0)
    params = {**moe.init_expert_params(jax.random.PRNGKey(0), D, F, held=held, n_outputs=held),
              "router": jnp.asarray(router), "router_bias": jnp.zeros((held,))}
    u = jnp.eye(D)[jnp.arange(96) % types] * 2.0
    live = np.arange(96) < 80
    kw = dict(n_routed=held, top_k=top_k, scale=1.0, rule=moe.route, live=jnp.asarray(live))
    assert moe.window_rows(96 * top_k, held, held) == 512 and moe._row_tile(512, 96 * top_k / held, D * F) == 128
    y, counts = jax.jit(lambda rows: moe.expert_layer(params, rows, **kw))(u)
    want, want_counts, pairs = plain(params, u, **kw)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    got = dict(zip(moe.COUNTS, np.asarray(counts).tolist()))
    assert (got["held"], got["touched"], got["peak"], got["windows"]) == (240, 48, 5, 1)
    assert got["pairs"] == pairs == got["touched"] + 1
    assert pairs_visited([5] * 48, 256) == 48
