"""K-EXAONE's language model on its tiny twin (CPU, float32): the paged programs
(two sections, a window or a full attention a layer by ``li % 4`` under a
``lax.cond``; rings with rotated keys in the state row beside a flat pool of
the full layers' rows alone; routing counts) against the one plain reference
(``benchmarks/reference/exaone_moe.py``) with contexts past the window, so that
a ring wraps in the prefill and again while decoding; the shares of the expert
layer against the uncut layer; a ring's rows permuted; a decode step dispatched
twice; the faults the comparison has to catch; the multi-token prediction
block; and the engine end to end."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import types  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks.families import exaone_moe as F  # noqa: E402
from benchmarks.reference import exaone_moe as R  # noqa: E402
from ray_tpu.models import exaone_moe as M, flat_kv, moe, paged  # noqa: E402
from ray_tpu.ops import paged_attention as PA, window_attention as WA  # noqa: E402
from ray_tpu.serve.llm.deployment import LLMServer, _resolve_model_cfg  # noqa: E402
from ray_tpu.serve.llm.kv_cache import BlockAllocator, BlockTable  # noqa: E402

# eight layers, two periods of LLLG: the dense layer 0 and expert layers 1-7; a window of 8 positions;
# experts 2..5 of 8 held
TWIN = dict(
    kind="exaone_moe", vocab_size=256, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_hidden_layers=8, first_k_dense_replace=1, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    num_experts=8, experts_held=4, expert_offset=2, num_shared_experts=1, num_experts_per_tok=3, n_group=1, topk_group=1,
    routed_scaling_factor=2.5, sliding_window=8, max_position_embeddings=256, rms_norm_eps=1e-5, rope_theta=100.0,
    dtype="float32",
)
BLOCK, BLOCKS, MAX_BLOCKS, ROWS, BUCKET = 4, 64, 17, 3, 32  # 16 columns of blocks and the state row's
STEPS = 12
PROMPT = np.random.default_rng(0).integers(1, 255, 21).tolist()  # 21 positions through a ring of 8: it wraps twice


def twin(**over):
    return _resolve_model_cfg({**TWIN, **over})


def weights(seed=0, **over):
    """The family's seeded weights (the benchmark's recipe) with the ``hyper``
    entry the reference reads; the program ignores it."""
    model = {k: v for k, v in {**TWIN, **over}.items() if k != "kind"}
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
    cfg = M.ExaoneMoeConfig()
    assert (cfg.n_full, cfg.n_window, cfg.n_expert_layers, cfg.kv_row, cfg.experts_held) == (12, 36, 47, 1024, 128)
    cut = M.ExaoneMoeConfig(num_hidden_layers=8, experts_held=16, vocab_size=19200)
    assert M.paged_block_bytes(cut, 16) == 2 * 16 * 8 * 128 * 2 * 2 == 131_072  # the two full layers' rows alone
    assert M.paged_ring(cut) == {"rows": 128, "bytes": 6 * 524_288} and M.paged_state_bytes(cut) == 3_145_728
    assert [M.is_full(i) for i in range(8)] == [R.is_full(i) for i in range(8)] == [False] * 3 + [True] + [False] * 3 + [True]
    for key, value in (("n_group", 2), ("topk_group", 2), ("scoring_func", "softmax"), ("norm_topk_prob", False)):
        with pytest.raises(ValueError, match="n_group, topk_group"):
            twin(**{key: value})
    with pytest.raises(ValueError, match="sliding_window_pattern"):
        twin(sliding_window_pattern="LG")
    with pytest.raises(ValueError, match="are not among"):
        twin(expert_offset=6)
    # multi-token prediction is a function of its own: the served path refuses the key by name
    drafting = twin(num_nextn_predict_layers=1)
    with pytest.raises(ValueError, match="num_nextn_predict_layers 1"):
        M.init_paged_pool(drafting, BLOCKS, BLOCK, ROWS + 1)
    with pytest.raises(ValueError, match="num_nextn_predict_layers 1"):
        programs(drafting)[0](weights(), jnp.zeros((1, 8), jnp.int32), jnp.zeros((1, MAX_BLOCKS), jnp.int32),
                              fresh_pool(twin()), jnp.int32(3))


def test_prefill_then_decode_steps_give_the_references_logits_past_the_window(served):
    """Tolerance 2e-4 of a position's logits in relative L2: both sides are
    float32 on the CPU, their sums in another order (the banded blocks, the
    grouped matmul); every fault below reads above 1e-2. The prompt's 21
    positions wrap a ring of 8 twice and the 12 steps wrap it again."""
    cfg, params, got, fed, pool, _ = served
    want = reference_logits(params, fed, len(PROMPT))
    assert got.shape == want.shape == (STEPS + 1, cfg.vocab_size)
    assert rel_err(got, want) < 2e-4
    # the decode steps' seven expert layers counted their rows: 12 steps x 7 layers x 3 choices
    counts = dict(zip(moe.COUNTS, np.asarray(pool["moe_counts"]).tolist()))
    assert counts["held"] + counts["absent"] == STEPS * 7 * 3 and counts["held"] > 0 and counts["zero"] == 0


FAULTS = {
    "a_window_of_7": "def window_of(hy):\n    return hy['sliding_window'] - 1\n",
    "a_window_of_9": "def window_of(hy):\n    return hy['sliding_window'] + 1\n",
    "a_rotary_on_the_full_layers": "def rotates(i):\n    return True\n",
    "no_norm_on_q_and_k": "def head_norm(x, weight, eps):\n    return x\n",
    "no_shared_expert": "def shared_part(u, w, at, precision):\n    return jnp.zeros_like(u)\n",
    "no_held_experts": "def routed_part(u, weights, chosen, w, at, hy, precision):\n    return jnp.zeros_like(u)\n",
    "weights_not_renormalised": "def renormalised(picked):\n    return picked\n",
    "a_scaling_factor_of_1": "def scaling(hy):\n    return 1.0\n",
}


def faulty(fault):
    module = types.ModuleType("faulty")
    exec(compile(open(R.__file__).read() + "\n\n" + FAULTS[fault], R.__file__, "exec"), module.__dict__)
    return module


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_reference_with_a_planted_fault_is_far_from_the_program(served, fault):
    """What `correct` has to catch (``benchmarks/tests/test_exaone_moe.py``
    plants the same in the cell's twin)."""
    cfg, params, got, fed, _, _ = served
    assert rel_err(got, reference_logits(params, fed, len(PROMPT), module=faulty(fault))) > 1e-2


# -- (b) the expert layer's shares ----------------------------------------------------


def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer_with_the_shared_expert_once():
    """Two chips of four experts each, and what every chip computes alike (the
    shared expert) counted once: the uncut reference's layer."""
    whole = weights(experts_held=8, expert_offset=0)
    hy = R.hyper(whole)
    u = jax.random.normal(jax.random.PRNGKey(3), (24, 64), jnp.float32)
    want = np.asarray(R.moe(u, whole, (2,), hy, "f32"))
    shared = np.asarray(R.shared_part(u, whole, (2,), "f32"))
    total = shared.copy()
    for offset in (0, 4):
        cfg = twin(experts_held=4, expert_offset=offset)
        share = {name: whole[name][:, offset:offset + 4] for name in ("e_gate", "e_up", "e_down")}
        own = lambda name, share=share: {**whole, **share}[name][2]  # noqa: E731
        y, counts = M._expert_ffn(cfg, own, share, u, 2, None)
        total += np.asarray(y) - shared
        assert int(counts[0]) + int(counts[2]) == 24 * 3  # held and absent: every (token, choice) row
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-4)


# -- (c) the rings ---------------------------------------------------------------------


def test_a_rings_live_rows_permuted_give_the_same_output():
    """The rotary is in the key: a key is rotated at its absolute position
    before it is written, so a score depends on ``t - s`` wherever position
    ``s`` lies in the ring, and the mask is a count. The same rows in another
    order, K and V permuted alike, are the same attention."""
    cfg = twin()
    rope = lambda x, pos: M.apply_rope(x, *M.rope_tables(pos, cfg.head_dim, cfg.rope_theta))  # noqa: E731
    key = jax.random.split(jax.random.PRNGKey(1), 3)
    t, (G, H, d, W) = 13, (cfg.num_key_value_heads, cfg.num_attention_heads, cfg.head_dim, cfg.sliding_window)
    positions = jnp.arange(t - W + 1, t + 1)
    k = rope(jax.random.normal(key[0], (W, G, d)), positions)
    v = jax.random.normal(key[1], (W, G, d))
    q = rope(jax.random.normal(key[2], (1, H, d)), jnp.asarray([t]))
    live = jnp.ones((1, W), bool)
    in_place = np.asarray(positions) % W  # where the program keeps them
    at = lambda order: WA.window_attention_rows(q, k[order][None], v[order][None], live, scale=d ** -0.5)  # noqa: E731
    ring = at(np.argsort(in_place))
    for order in (np.arange(W), np.random.default_rng(4).permutation(W)):
        np.testing.assert_allclose(np.asarray(at(order)), np.asarray(ring), atol=1e-6, rtol=1e-5)
    # and a key rotated where it lies (by its row, not its position) is another attention
    wrong = WA.window_attention_rows(q, rope(jax.random.normal(key[0], (W, G, d)), jnp.arange(W))[None], v[None], live,
                                     scale=d ** -0.5)
    assert float(jnp.abs(wrong - at(np.arange(W))).max()) > 1e-2


def test_the_same_decode_step_dispatched_twice_leaves_the_pool_bit_for_bit(served):
    """The benchmark's replay calls ``decode_step`` and then
    ``decode_step_greedy`` on the same arguments: a ring's and the full layers'
    rows are written again, the same; the routing counts alone go on."""
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
        row, blocks = table.state_row, np.asarray(table.blocks)
        for name in ("ring_k", "ring_v"):
            np.testing.assert_array_equal(kept[name][:, row], np.asarray(pool[name])[:, row], err_msg=name)
        G = cfg.num_key_value_heads
        mine = ((blocks[:, None] * BLOCK + np.arange(BLOCK)).reshape(-1)[:, None] * G + np.arange(G)).reshape(-1)
        np.testing.assert_array_equal(kept["kv"][:, :, mine], np.asarray(pool["kv"])[:, :, mine])
        assert np.abs(kept["ring_k"][:, row]).max(axis=-1).all() and np.abs(kept["kv"][:, 0, mine[: table.length * G]]).max(axis=-1).all()


def test_a_decode_step_with_the_paged_kernel_in_it_is_the_step_that_scatters_and_gathers(served, monkeypatch):
    """The decode step on the path it takes on a TPU (the paged kernel writes
    the full layers' rows; here in interpret mode) against the path it takes
    elsewhere (``write_spans`` and the gathered table): twelve steps from
    position 21 through three block boundaries, an inactive slot either side."""
    cfg, params, gathered, _, pool, table = served
    _, fresh, _ = prefill_into(cfg, params, fresh_pool(cfg), BlockAllocator(BLOCKS, BLOCK, state_rows=ROWS), PROMPT)
    monkeypatch.setattr(flat_kv, "can_use_paged_kernel", lambda *_: True)
    traced = []
    monkeypatch.setattr(flat_kv, "paged_decode_attention", lambda *a, **kw: traced.append(kw["new_k"].shape) or PA.paged_decode_attention(
        *a, **kw, interpret=True))
    kernel, _, kernel_pool, kernel_table = run_paged(cfg, params, PROMPT)
    G, d = cfg.num_key_value_heads, cfg.head_dim
    assert traced == [(3, G, d)] * 2  # a trace a section with a full layer, in the decode step alone
    np.testing.assert_allclose(kernel, gathered, atol=2e-4, rtol=2e-4)
    assert kernel_table.blocks == table.blocks
    rows = (np.asarray(table.blocks)[:, None] * BLOCK + np.arange(BLOCK)).reshape(-1)[:table.length]
    mine = (rows[:, None] * G + np.arange(G)).reshape(-1)
    for plane in (0, 1):  # keys, values
        got, want = np.asarray(kernel_pool["kv"][:, plane]), np.asarray(pool["kv"][:, plane])
        np.testing.assert_allclose(got[:, mine], want[:, mine], atol=2e-5, rtol=2e-5)
        assert np.abs(got[:, mine]).max(axis=-1).all()  # every position's row written, a prompt's and a step's
        # the null block: as the prefill left it (the inactive slots wrote nothing), where the scatter went on writing
        np.testing.assert_array_equal(got[:, :BLOCK * G], np.asarray(fresh["kv"][:, plane])[:, :BLOCK * G])
        assert (want[:, :BLOCK * G] != got[:, :BLOCK * G]).any()


def test_a_prompt_shorter_than_its_bucket_leaves_the_rows_of_an_exact_length_pass():
    cfg, params = twin(), weights()
    rows = {}
    for bucket in (len(PROMPT) + 3, 32, 64):  # 21 tokens in 24, 32 and 64 positions
        alloc = BlockAllocator(BLOCKS, BLOCK, state_rows=ROWS)
        logits, pool, table = prefill_into(cfg, params, fresh_pool(cfg), alloc, PROMPT, bucket=bucket)
        rows[bucket] = (logits, *(np.asarray(pool[k][:, table.state_row]) for k in ("ring_k", "ring_v")))
    for bucket in (32, 64):
        for a, b in zip(rows[24], rows[bucket]):
            np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)
    # a ring holds the last 8 positions' rows, position p at p % 8: every row written
    assert np.abs(rows[32][1].reshape(cfg.n_window, cfg.sliding_window, -1)).max(axis=-1).all()


# -- (d) multi-token prediction: written down, compared, not served -----------------------


def test_the_mtp_block_gives_the_references_logits():
    cfg = twin(num_nextn_predict_layers=1, experts_held=8, expert_offset=0)
    params = weights(num_nextn_predict_layers=1, experts_held=8, expert_offset=0)
    assert set(params["mtp"]) == {"h_norm", "e_norm", "proj", "in_norm", "post_norm", "wqkv", "q_norm", "k_norm", "wo",
                                  "router", "router_bias", "e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down"}
    tokens = np.random.default_rng(2).integers(1, 255, 24).astype(np.int32)
    rows = np.arange(0, 22)
    hidden = R.hidden_states(params, tokens)
    got = np.asarray(M.mtp_logits(cfg, params, hidden, jnp.asarray(tokens)))[rows]
    want = np.asarray(R.mtp_logits_at(params, tokens, rows))
    assert got.shape == want.shape == (22, 256) and rel_err(got, want) < 2e-4
    # it is another head than the model's own: the next token's logits are not the token after's
    assert rel_err(got, np.asarray(R.logits_at(params, tokens, rows))) > 0.1
    assert set(jax.eval_shape(lambda k: M.init_params(k, cfg), jax.random.PRNGKey(0))["mtp"]) == set(params["mtp"])


# -- (e) the engine ------------------------------------------------------------------


def test_the_engine_serves_twice_its_slots_and_a_step_leaves_blocks_ring_rows_and_routing_counts_together():
    """No new allocation for this kind, but the first that fills ``kv_blocks``
    (the full layers' blocks), ``ring_rows`` and the ``llm_moe`` record in the
    same step. Telemetry's buffer is stood in for (no cluster is connected
    here), so the loop keeps its records."""

    class Buffer:
        def record_loop(self, stem, rec):
            pass

    engine = dict(block_size=BLOCK, num_blocks=BLOCKS, max_batch=2, max_blocks_per_seq=MAX_BLOCKS)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 255, n).tolist() for n in (5, 13, 9, 21)]
    server = LLMServer(TWIN, engine, weight_seed=4)
    try:
        eng = server._engine
        eng._tel = Buffer()
        stats = server.kv_stats()
        assert (stats["state_rows_total"], stats["state_rows_used"]) == (2, 0)
        ring = 6 * 2 * 8 * 2 * 16 * 4  # six window layers, K and V, 8 rows of two heads of 16 float32
        assert stats["ring_bytes"] == stats["state_bytes"] == M.paged_state_bytes(eng.model_cfg) == ring
        assert stats["bytes_per_block"] == 2 * 2 * BLOCK * 32 * 4  # K and V of the two full layers, and of no other
        assert eng.max_context == (MAX_BLOCKS - 1) * BLOCK and eng._moe_layers == 7
        assert eng._pool["ring_k"].shape[:2] == (6, 3) and eng._pool["kv"].shape[:2] == (2, 2)
        streams = [server.generate(p, max_new_tokens=10) for p in prompts]  # four requests on two slots
        together = [list(s) for s in streams]
        alone = [list(server.generate(p, max_new_tokens=10)) for p in prompts]
        assert together == alone and all(len(t) == 10 for t in together)
        stats = server.kv_stats()
        assert stats["state_rows_used"] == 0 and stats["blocks_free"] == stats["blocks_total"]  # rings and blocks are back
        eng._moe_copy = (M.routing_counts(eng._pool), eng.decode_steps)
        eng._fold_routing_counts()
        loop = server.loop_stats(records=4096)
        assert loop["state_rows_total"] == 2 and loop["ring_bytes"] == ring
        live = [r for r in (dict(zip(loop["fields"], r)) for r in loop["records"]) if r["live"]]
        # a step's blocks are the full layers' (a sequence of n positions holds ceil(n / 4) of them) and its
        # ring rows at most the window a sequence
        assert live and all(0 < r["ring_rows"] <= 8 * r["live"] and r["live"] <= r["kv_blocks"] <= 8 * r["live"] for r in live)
        newest = loop["moe"]
        assert newest["layers"] == 7 and newest["held"] + newest["absent"] == sum(r["live"] for r in live) * 7 * 3
        assert 0 < newest["touched"] <= newest["held"] and newest["zero"] == 0
        # and they are the model's tokens: the reference's argmax over what was fed
        model = {k: v for k, v in TWIN.items() if k != "kind"}
        hyper = {**{k: np.int32(model[k]) for k in F.HYPER_INT}, **{k: np.float32(model[k]) for k in F.HYPER_FLOAT}}
        seq = np.zeros((64,), np.int32)
        fed = prompts[3] + together[3]
        seq[: len(fed)] = fed
        want = np.asarray(R.logits_at({**eng.params, "hyper": hyper}, seq, np.arange(len(prompts[3]) - 1, len(fed) - 1),
                                      "f32")).argmax(-1)
        assert want.tolist() == together[3]
    finally:
        server._engine.shutdown()
