"""LFM2-MoE's language model on its tiny twin (CPU, float32): the paged programs
(three sections: dense conv conv; expert full conv; expert conv conv full conv;
a conv window a sequence in the state row beside a flat pool of the full
layers' rows, two K/V heads to a row; routing counts) against the one plain
reference (``benchmarks/reference/lfm2_moe.py``), with prompts shorter than the
convolution's width and as long as their bucket; the faults the comparison has
to catch; a sequence among neighbours and in another slot; a decode step
dispatched twice; the router's bias and epsilon; the packed rows through the
paged kernel in interpret mode; the shares of the expert layer against the
whole layer, which is what the configuration serves; and the engine end to
end."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import types  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks.families import lfm2_moe as F  # noqa: E402
from benchmarks.reference import lfm2_moe as R  # noqa: E402
from ray_tpu.models import lfm2_moe as M, moe, paged  # noqa: E402
from ray_tpu.ops import paged_attention as PA  # noqa: E402
from ray_tpu.ops.window_attention import window_attention_rows  # noqa: E402
from ray_tpu.serve.llm.deployment import LLMServer, _resolve_model_cfg  # noqa: E402
from ray_tpu.serve.llm.kv_cache import BlockAllocator, BlockTable  # noqa: E402

# eight layers in the published pattern (conv conv full conv, twice), the first two dense; four query heads over two
# K/V heads of 16 (both in one pool row of 32); eight experts, two a token, all held
TWIN = dict(
    kind="lfm2_moe", vocab_size=256, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_hidden_layers=8, num_dense_layers=2, num_attention_heads=4, num_key_value_heads=2, num_experts=8,
    num_experts_per_tok=2, routed_scaling_factor=1.0, conv_L_cache=3, max_position_embeddings=256, norm_eps=1e-5,
    rope_theta=100.0, dtype="float32",
)
BLOCK, BLOCKS, MAX_BLOCKS, ROWS, BUCKET = 8, 32, 9, 3, 32  # 8 columns of blocks (a block a whole float32 tile) and the state row's
STEPS = 12
PROMPT = np.random.default_rng(0).integers(1, 255, 21).tolist()


def twin(**over):
    return _resolve_model_cfg({**TWIN, **over})


def weights(seed=0, **over):
    """The family's seeded weights (the benchmark's recipe) with the ``hyper``
    entry the reference reads; the program ignores it."""
    model = {"experts_held": 8, "expert_offset": 0, **{k: v for k, v in {**TWIN, **over}.items() if k != "kind"}}
    return jax.jit(lambda w: F.make_weights(w, model, jnp.float32))(jnp.asarray([seed, 7], jnp.uint32))


def programs(cfg):
    return paged.make_paged_fns(M.paged_layer, cfg, block_size=BLOCK, state_rows=True)


def fresh_pool(cfg):
    return M.init_paged_pool(cfg, BLOCKS, BLOCK, ROWS + 1)


def prefill_into(cfg, params, pool, alloc, prompt, bucket=BUCKET, fns=None):
    prefill = (fns or programs(cfg))[0]
    table = BlockTable(alloc)
    table.reserve(len(prompt))
    table.length = len(prompt)
    toks = np.zeros((1, bucket), np.int32)
    toks[0, : len(prompt)] = prompt
    logits, pool = prefill(params, jnp.asarray(toks), jnp.asarray([table.as_list(MAX_BLOCKS)], jnp.int32), pool,
                           jnp.int32(len(prompt)))
    return np.asarray(logits[0]), pool, table


def step_args(table, token, batch=3, slot=1):
    tk, ps = np.zeros((batch,), np.int32), np.zeros((batch,), np.int32)
    bt, ac = np.zeros((batch, MAX_BLOCKS), np.int32), np.zeros((batch,), bool)
    tk[slot], ps[slot], ac[slot] = token, table.length, True
    table.append_token()
    bt[slot] = table.as_list(MAX_BLOCKS)
    return jnp.asarray(tk), jnp.asarray(ps), jnp.asarray(bt), jnp.asarray(ac)


def run_paged(cfg, params, prompt, steps=STEPS, slot=1):
    """Prefill ``prompt``, then ``steps`` greedy decode steps in ``slot`` of a
    batch of three. -> (logits of every position fed (steps + 1, V), tokens
    fed, the pool, the table)."""
    fns = programs(cfg)
    alloc = BlockAllocator(BLOCKS, BLOCK, state_rows=ROWS)
    first, pool, table = prefill_into(cfg, params, fresh_pool(cfg), alloc, prompt, fns=fns)
    got, fed = [first], list(prompt)
    for _ in range(steps):
        tk, ps, bt, ac = step_args(table, int(got[-1].argmax()), slot=slot)
        fed.append(int(tk[slot]))
        logits, pool = fns[1](params, tk, ps, bt, pool, ac)
        got.append(np.asarray(logits[slot]))
    return np.stack(got), fed, pool, table


def reference_logits(params, fed, n_prompt, steps=STEPS, module=R):
    seq = np.zeros((64,), np.int32)
    seq[: len(fed)] = fed
    return np.asarray(module.logits_at(params, seq, np.arange(n_prompt - 1, n_prompt + steps), "f32"))


def rel_err(got, want):
    return float((np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)).max())


@pytest.fixture(scope="module")
def served():
    cfg = twin()
    params = weights()
    got, fed, pool, table = run_paged(cfg, params, PROMPT)
    return cfg, params, got, fed, pool, table


# -- (a) the paged programs against the reference's full forward pass ----------


def test_the_config_counts_the_published_layers_and_refuses_what_the_program_does_not_run():
    cfg = M.Lfm2MoeConfig()
    assert (cfg.n_full, cfg.n_conv, cfg.n_expert_layers, cfg.head_dim, cfg.kv_row, cfg.kv_pack, cfg.experts_held) == (
        10, 30, 38, 64, 512, 2, 64)
    cut = M.Lfm2MoeConfig(num_hidden_layers=8)
    assert M.paged_block_bytes(cut, 16) == 2 * 2 * 16 * 512 * 2 == 65_536  # 4,096 B a position: the two full layers' rows alone
    assert M.paged_state_bytes(cut) == 6 * (3 * 2048 * 2 + 4) == 73_752  # the six conv layers' windows and their counts
    assert [M.is_full(i) for i in range(8)] == [R.is_full(i) for i in range(8)] == [False, False, True, False] * 2
    assert [(lo, hi, each) for lo, hi, each in M._runs(0, 2) + M._runs(2, 40)] == [(0, 2, 2), (2, 4, 2), (4, 40, 4)]
    assert twin().kv_pack == 2 and twin(num_key_value_heads=1).kv_pack == 1
    assert M.Lfm2MoeConfig(hidden_size=4096).kv_pack == 1  # heads of 128 fill a row alone
    for key, value in (("norm_topk_prob", False), ("use_expert_bias", False), ("conv_bias", True), ("tie_word_embeddings", False)):
        with pytest.raises(ValueError, match="norm_topk_prob"):
            twin(**{key: value})
    with pytest.raises(ValueError, match="layer_types"):
        twin(layer_types=["conv", "full_attention"] * 4)
    assert twin(layer_types=["conv", "conv", "full_attention", "conv"] * 2) == twin()
    with pytest.raises(ValueError, match="are not among"):
        twin(experts_held=4, expert_offset=6)
    assert "unembed" not in jax.eval_shape(lambda k: M.init_params(k, twin()), jax.random.PRNGKey(0))


def test_prefill_then_decode_steps_give_the_references_logits(served):
    """Tolerance 2e-4 of a position's logits in relative L2: both sides are
    float32 on the CPU, their sums in another order (the convolution's taps,
    the grouped matmul, the attention's blocks); every fault below reads above
    1e-2."""
    cfg, params, got, fed, pool, _ = served
    want = reference_logits(params, fed, len(PROMPT))
    assert got.shape == want.shape == (STEPS + 1, cfg.vocab_size)
    assert rel_err(got, want) < 2e-4
    # the decode steps' six expert layers counted their rows: 12 steps x 6 layers x 2 choices, every expert held
    counts = dict(zip(moe.COUNTS, np.asarray(pool["moe_counts"]).tolist()))
    assert (counts["held"], counts["absent"], counts["zero"], counts["windows"]) == (STEPS * 6 * 2, 0, 0, STEPS * 6)


@pytest.mark.parametrize("length", [1, 2, 3, 8, 32])
def test_a_prompt_shorter_than_the_convolutions_width_and_one_that_ends_its_bucket(length):
    """One and two tokens (the window's other products are zeros, as before the
    sequence's start), three (the window is the prompt), a whole block, and a
    prompt that fills its bucket of 32 (no padded position); then four steps."""
    cfg, params = twin(), weights()
    prompt = np.random.default_rng(length).integers(1, 255, length).tolist()
    got, fed, pool, table = run_paged(cfg, params, prompt, steps=4)
    assert rel_err(got, reference_logits(params, fed, length, steps=4)) < 2e-4
    assert np.asarray(pool["state_pos"])[:, table.state_row].tolist() == [length + 4] * 6


FAULTS = {
    "the_convolution_one_position_late": "_delayed = delayed\n\n\ndef delayed(s, back):\n    return _delayed(s, back + 1)\n",
    "no_second_gate": "def gated(gate, c):\n    return c\n",
    "no_norm_on_q_and_k": "def head_norm(x, weight, eps):\n    return x\n",
    "no_experts": "def routed_part(u, weights, chosen, w, at, hy, precision):\n    return jnp.zeros_like(u)\n",
    "weights_not_renormalised": "def renormalised(picked):\n    return picked\n",
    "the_taps_in_the_other_order": "_delayed = delayed\n\n\ndef delayed(s, back):\n    return _delayed(s, 2 - back)\n",
}


def faulty(fault):
    module = types.ModuleType("faulty")
    exec(compile(open(R.__file__).read() + "\n\n" + FAULTS[fault], R.__file__, "exec"), module.__dict__)
    return module


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_reference_with_a_planted_fault_is_far_from_the_program(served, fault):
    """What `correct` has to catch (``benchmarks/tests/test_lfm2_moe.py``
    plants the same in the cell's twin)."""
    cfg, params, got, fed, _, _ = served
    assert rel_err(got, reference_logits(params, fed, len(PROMPT), module=faulty(fault))) > 1e-2


# -- (b) a sequence is its own ------------------------------------------------------


@pytest.mark.parametrize("slot", [0, 2])
def test_a_sequences_logits_do_not_depend_on_its_slot(served, slot):
    cfg, params, got, _, _, _ = served
    again, _, _, _ = run_paged(cfg, params, PROMPT, slot=slot)
    np.testing.assert_array_equal(again, got)


def test_a_sequences_logits_do_not_depend_on_its_neighbours(served):
    """Two other sequences prefilled into the same pool and stepped in the
    slots beside it: its window, its rows and its experts' results are its
    own."""
    cfg, params, got, fed, _, _ = served
    fns = programs(cfg)
    alloc = BlockAllocator(BLOCKS, BLOCK, state_rows=ROWS)
    pool, tables = fresh_pool(cfg), []
    rng = np.random.default_rng(5)
    for prompt in (rng.integers(1, 255, 7).tolist(), PROMPT, rng.integers(1, 255, 30).tolist()):
        _, pool, table = prefill_into(cfg, params, pool, alloc, prompt, fns=fns)
        tables.append(table)
    mine = []
    for token in fed[len(PROMPT):len(PROMPT) + 6]:
        tk, ps, bt = np.zeros((3,), np.int32), np.zeros((3,), np.int32), np.zeros((3, MAX_BLOCKS), np.int32)
        for slot, table in enumerate(tables):
            tk[slot], ps[slot] = (token if slot == 1 else int(rng.integers(1, 255))), table.length
            table.append_token()
            bt[slot] = table.as_list(MAX_BLOCKS)
        logits, pool = fns[1](params, jnp.asarray(tk), jnp.asarray(ps), jnp.asarray(bt), pool, jnp.ones((3,), bool))
        mine.append(np.asarray(logits[1]))
    # not bit for bit: a batch of three live rows sums a matmul's rows in another grouping than one live row among zeros
    np.testing.assert_allclose(np.stack(mine), got[1:7], atol=2e-5, rtol=2e-5)


def test_the_same_decode_step_dispatched_twice_leaves_the_window_and_the_rows_bit_for_bit(served):
    """The benchmark's replay calls ``decode_step`` and then
    ``decode_step_greedy`` on the same arguments: the conv window takes the
    position in once (``state_pos``), the full layers' rows are written again,
    the same; the routing counts alone go on."""
    cfg, params, _, fed, _, _ = served
    _, decode, greedy = fns = programs(cfg)
    alloc = BlockAllocator(BLOCKS, BLOCK, state_rows=ROWS)
    _, pool, table = prefill_into(cfg, params, fresh_pool(cfg), alloc, PROMPT, fns=fns)
    for token in fed[len(PROMPT):len(PROMPT) + 4]:
        args = step_args(table, token)
        once, pool = decode(params, *args[:3], pool, args[3])
        kept = jax.tree.map(np.asarray, pool)
        twice, pool = decode(params, *args[:3], pool, args[3])
        tokens, pool = greedy(params, *args[:3], pool, args[3])
        np.testing.assert_array_equal(np.asarray(once), np.asarray(twice))
        assert int(tokens[1]) == int(np.asarray(once)[1].argmax())
        for name in ("conv", "state_pos", "kv"):
            np.testing.assert_array_equal(kept[name], np.asarray(pool[name]), err_msg=name)
        row = table.state_row
        assert kept["state_pos"][:, row].tolist() == [table.length] * 6 and np.abs(kept["conv"][:, row]).max(axis=-1).all()
        assert not kept["conv"][:, 0].any() and not kept["state_pos"][:, 0].any()  # the null row is nobody's
    # and the window is the last three products, the oldest first: a step moved it on by one
    before = kept["conv"][:, row].reshape(6, 3, 64)
    args = step_args(table, fed[len(PROMPT) + 4])
    _, pool = decode(params, *args[:3], pool, args[3])
    after = np.asarray(pool["conv"])[:, row].reshape(6, 3, 64)
    np.testing.assert_array_equal(after[:, :2], before[:, 1:])


# -- (c) the router ---------------------------------------------------------------------


def test_the_bias_moves_the_choice_and_not_the_weights_and_the_epsilon_is_the_published_one():
    u = jax.random.normal(jax.random.PRNGKey(2), (16, 64), jnp.float32)
    router = jax.random.normal(jax.random.PRNGKey(3), (64, 8), jnp.float32) * 64 ** -0.5
    none = jnp.zeros((8,))
    _, plain = M.ROUTE(u, router, none, top_k=2, scale=1.0)
    bias = none.at[5].set(10.0)  # expert 5 into every token's choice
    w, e = M.ROUTE(u, router, bias, top_k=2, scale=1.0)
    assert (np.asarray(e) == 5).any(axis=-1).all() and not (np.asarray(plain) == 5).any(axis=-1).all()
    s = np.asarray(jax.nn.sigmoid(u @ router))
    picked = np.take_along_axis(s, np.asarray(e), axis=-1)
    np.testing.assert_allclose(np.asarray(w), picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)  # the scores, unbiased
    assert np.asarray(w).max() < 1.0 < (picked + 10.0).max()
    # the reference routes alike
    rw, re_ = R.route(u, router, bias, {"num_experts_per_tok": 2, "routed_scaling_factor": 1.0}, "f32")
    np.testing.assert_array_equal(np.asarray(re_), np.asarray(e))
    np.testing.assert_allclose(np.asarray(rw), np.asarray(w), rtol=1e-6)
    # two scores of 1e-7: under DeepSeek-V3's 1e-20 the weights add up to 1, under the published 1e-6 to 2e-7 / 1.2e-6
    faint = jnp.full((1, 8), float(jax.scipy.special.logit(1e-7)))  # the logits of a token of 1 through this "router"
    got, _ = M.ROUTE(jnp.ones((1, 1)), faint, none, top_k=2, scale=1.0)
    other, _ = moe.route_sigmoid(jnp.ones((1, 1)), faint, none, top_k=2, scale=1.0)
    assert float(got.sum()) == pytest.approx(1 / 6, rel=1e-3) and float(other.sum()) == pytest.approx(1.0, rel=1e-3)
    assert M.ROUTE_EPS == 1e-6


# -- (d) two K/V heads to a pool row ----------------------------------------------------


def test_attention_over_rows_of_two_packed_heads_is_the_attention_a_head_at_a_time():
    """The paged kernel (interpret mode) over a flat pool whose rows hold two
    K/V heads, queries zero-filled outside their own head's half and the
    output's own half kept, against a softmax a query head over its own K/V
    head's keys: three sequences of 5, 0 and 19 positions in blocks of 8."""
    cfg = twin()
    H, G, d, P = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim, cfg.kv_pack
    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    lengths = np.asarray([5, 0, 19])
    tables = np.asarray([[3, 0, 0], [0, 0, 0], [7, 2, 5]], np.int32)
    k, v = (jax.random.normal(key, (BLOCKS * BLOCK, G, d), jnp.float32) for key in keys[:2])
    q = jax.random.normal(keys[2], (3, H, d), jnp.float32)
    pool = jnp.stack([k, v]).reshape(1, 2, BLOCKS * BLOCK * G // P, P * d)  # keys in plane 0, values in plane 1
    packed = M.pack_queries(cfg, q)
    assert packed.shape == (3, H, P * d) and float(jnp.abs(packed).sum()) == pytest.approx(float(jnp.abs(q).sum()), rel=1e-6)
    o = PA.paged_decode_attention(packed, pool, 0, jnp.asarray(tables), jnp.asarray(lengths), block_size=BLOCK,
                                  kv_heads=G // P, scale=d ** -0.5, interpret=True)
    got = np.asarray(M.unpack_outputs(cfg, o))
    slots = (tables[:, :, None] * BLOCK + np.arange(BLOCK)).reshape(3, -1)
    for b, n in enumerate(lengths):
        for h in range(H):
            g = h // (H // G)
            kk, vv = np.asarray(k)[slots[b, :n], g], np.asarray(v)[slots[b, :n], g]
            p = np.exp((kk @ np.asarray(q)[b, h]) * d ** -0.5)
            want = (p / p.sum()) @ vv if n else np.zeros((d,))
            np.testing.assert_allclose(got[b, h], want, atol=2e-5, rtol=2e-5)
    # and it is what the gathered path computes over the same rows seen a head at a time
    rows = window_attention_rows(q, jnp.asarray(np.asarray(k)[slots]), jnp.asarray(np.asarray(v)[slots]),
                                 jnp.arange(slots.shape[1])[None, :] < lengths[:, None], scale=d ** -0.5)
    np.testing.assert_allclose(got, np.asarray(rows), atol=2e-5, rtol=2e-5)


def test_a_decode_step_with_the_paged_kernel_in_it_is_the_step_that_scatters_and_gathers(served, monkeypatch):
    """The decode step on the path it takes on a TPU (the paged kernel writes
    the full layers' packed rows; here in interpret mode) against the path it
    takes elsewhere (``write_spans`` and the gathered table): twelve steps from
    position 21 through a block boundary, an inactive slot either side."""
    cfg, params, gathered, _, pool, table = served
    _, fresh, _ = prefill_into(cfg, params, fresh_pool(cfg), BlockAllocator(BLOCKS, BLOCK, state_rows=ROWS), PROMPT)
    monkeypatch.setattr(M, "can_use_paged_kernel", lambda *_: True)
    traced = []
    monkeypatch.setattr(M, "paged_decode_attention", lambda *a, **kw: traced.append((a[0].shape, kw["new_k"].shape, kw["kv_heads"]))
                        or PA.paged_decode_attention(*a, **kw, interpret=True))
    kernel, _, kernel_pool, kernel_table = run_paged(cfg, params, PROMPT)
    assert traced == [((3, 4, 32), (3, 1, 32), 1)] * 2  # a trace a section with a full layer, in the decode step alone
    np.testing.assert_allclose(kernel, gathered, atol=2e-4, rtol=2e-4)
    assert kernel_table.blocks == table.blocks
    mine = (np.asarray(table.blocks)[:, None] * BLOCK + np.arange(BLOCK)).reshape(-1)[:table.length]  # a position a row here
    for plane in (0, 1):  # keys, values
        got, want = np.asarray(kernel_pool["kv"][:, plane]), np.asarray(pool["kv"][:, plane])
        np.testing.assert_allclose(got[:, mine], want[:, mine], atol=2e-5, rtol=2e-5)
        assert np.abs(got[:, mine]).max(axis=-1).all()  # every position's row written, a prompt's and a step's
        # the null block: as the prefill left it (the inactive slots wrote nothing), where the scatter went on writing
        np.testing.assert_array_equal(got[:, :BLOCK], np.asarray(fresh["kv"][:, plane])[:, :BLOCK])
        assert (want[:, :BLOCK] != got[:, :BLOCK]).any()


# -- (e) the expert layer's shares ----------------------------------------------------


@pytest.mark.parametrize("chips,held", [(2, 4), (4, 2)])
def test_the_shares_of_the_expert_layer_add_up_to_the_whole_layer_which_is_what_is_served(chips, held):
    """``chips`` chips of ``held`` experts each (``expert_offset``): their
    parts add up to the uncut reference's layer, and to the program's own
    layer with every expert held, the configuration's deployment. No shared
    expert: nothing is counted once."""
    whole = weights()
    hy = R.hyper(whole)
    u = jax.random.normal(jax.random.PRNGKey(3), (24, 64), jnp.float32)
    want = np.asarray(R.moe(u, whole, 2, hy, "f32"))
    own = lambda name: whole[name][2]  # noqa: E731
    served_layer, counts = M._expert_ffn(twin(), own, whole, u, 2, None)
    assert (int(counts[0]), int(counts[2])) == (24 * 2, 0)  # every (token, choice) row held
    np.testing.assert_allclose(np.asarray(served_layer), want, atol=2e-5, rtol=2e-4)
    total = np.zeros_like(want)
    for offset in range(0, 8, held):
        cfg = twin(experts_held=held, expert_offset=offset)
        share = {name: whole[name][:, offset:offset + held] for name in ("e_gate", "e_up", "e_down")}
        y, counts = M._expert_ffn(cfg, own, share, u, 2, None)
        total += np.asarray(y)
        assert int(counts[0]) + int(counts[2]) == 24 * 2  # held and absent: every (token, choice) row
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-4)


# -- (f) the engine ------------------------------------------------------------------


def test_the_engine_serves_twice_its_slots_with_each_request_as_if_alone():
    """Four requests on two slots: state rows and blocks handed out and back,
    the ``llm_moe`` counts of a kind whose every expert is held, and the
    model's own tokens (the reference's argmax over what was fed). Telemetry's
    buffer is stood in for (no cluster is connected here), so the loop keeps
    its records."""

    class Buffer:
        def record_loop(self, stem, rec):
            pass

    engine = dict(block_size=BLOCK, num_blocks=BLOCKS, max_batch=2, max_blocks_per_seq=MAX_BLOCKS)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 255, n).tolist() for n in (2, 13, 9, 21)]
    server = LLMServer(TWIN, engine, weight_seed=4)
    try:
        eng = server._engine
        eng._tel = Buffer()
        stats = server.kv_stats()
        assert (stats["state_rows_total"], stats["state_rows_used"]) == (2, 0)
        assert stats["state_bytes"] == M.paged_state_bytes(eng.model_cfg) == 6 * (3 * 64 * 4 + 4) and stats["ring_bytes"] == 0
        assert stats["bytes_per_block"] == 2 * 2 * BLOCK * 32 * 4  # K and V of the two full layers, and of no other
        assert eng.max_context == (MAX_BLOCKS - 1) * BLOCK and eng._moe_layers == 6
        assert eng._pool["conv"].shape == (6, 3, 192) and eng._pool["kv"].shape == (2, 2, BLOCKS * BLOCK, 32)
        streams = [server.generate(p, max_new_tokens=10) for p in prompts]  # four requests on two slots
        together = [list(s) for s in streams]
        alone = [list(server.generate(p, max_new_tokens=10)) for p in prompts]
        assert together == alone and all(len(t) == 10 for t in together)
        stats = server.kv_stats()
        assert stats["state_rows_used"] == 0 and stats["blocks_free"] == stats["blocks_total"]  # windows and blocks are back
        eng._moe_copy = (M.routing_counts(eng._pool), eng.decode_steps)
        eng._fold_routing_counts()
        loop = server.loop_stats(records=4096)
        live = [r for r in (dict(zip(loop["fields"], r)) for r in loop["records"]) if r["live"]]
        assert live and all(not r.get("ring_rows") and r["live"] <= r["kv_blocks"] <= 4 * r["live"] for r in live)
        newest = loop["moe"]
        assert newest["layers"] == 6 and newest["held"] == sum(r["live"] for r in live) * 6 * 2
        assert 0 < newest["touched"] <= newest["held"] and newest["zero"] == newest["absent"] == 0
        model = {"experts_held": 8, "expert_offset": 0, **{k: v for k, v in TWIN.items() if k != "kind"}}
        hyper = {**{k: np.int32(model[k]) for k in F.HYPER_INT}, **{k: np.float32(model[k]) for k in F.HYPER_FLOAT}}
        seq = np.zeros((64,), np.int32)
        fed = prompts[3] + together[3]
        seq[: len(fed)] = fed
        want = np.asarray(R.logits_at({**eng.params, "hyper": hyper}, seq, np.arange(len(prompts[3]) - 1, len(fed) - 1),
                                      "f32")).argmax(-1)
        assert want.tolist() == together[3]
    finally:
        server._engine.shutdown()
