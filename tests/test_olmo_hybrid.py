"""Olmo-Hybrid's language model on its tiny twin (CPU, float32): the paged
programs (one section whose body is a period: linear-attention layers with a
state row a sequence, full-attention layers over paged K/V) against the one
plain reference (``benchmarks/reference/olmo_hybrid.py``), what a padded prefill
leaves in a state row, a decode step dispatched twice, a state row's second
owner, the faults the comparison has to catch, and the engine end to end."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks.families import olmo_hybrid as F  # noqa: E402
from benchmarks.reference import olmo_hybrid as R  # noqa: E402
from ray_tpu.models import olmo_hybrid as M, paged  # noqa: E402
from ray_tpu.serve.llm.deployment import LLMServer, _resolve_model_cfg  # noqa: E402
from ray_tpu.serve.llm.kv_cache import BlockAllocator, BlockTable  # noqa: E402

# two periods of (linear, linear, full): both kinds stacked over more than one period
TWIN = dict(
    kind="olmo_hybrid", vocab_size=256, hidden_size=64, intermediate_size=96, num_hidden_layers=6,
    num_attention_heads=4, num_key_value_heads=4, max_position_embeddings=256, rms_norm_eps=1e-6,
    layer_types=["linear_attention", "linear_attention", "full_attention"] * 2, linear_num_key_heads=4,
    linear_num_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=16, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, dtype="float32",
)
BLOCK, BLOCKS, MAX_BLOCKS, ROWS, BUCKET = 4, 64, 17, 3, 32  # 16 columns of blocks and the state row's
STEPS = 12
PROMPT = np.random.default_rng(0).integers(1, 255, 21).tolist()


def twin(**over):
    return _resolve_model_cfg({**TWIN, **over})


def weights(cfg, seed=0):
    """The family's seeded weights (the benchmark's recipe) with the ``hyper``
    entry the reference reads; the program ignores it."""
    model = F.model_kwargs({**TWIN, "rope_parameters": {"rope_theta": None}})
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
    params = weights(cfg)
    got, fed, pool, table = run_paged(cfg, params, PROMPT)
    return cfg, params, got, fed, pool, table


# -- (a) the paged programs against the reference's full forward pass ----------


def test_the_config_reads_the_published_period_and_refuses_what_the_program_does_not_run():
    cfg = M.OlmoHybridConfig()
    assert cfg.period == M.PERIOD and (cfg.n_linear, cfg.n_full, cfg.head_dim) == (24, 8, 128)
    assert cfg.kv_heads_stored == 32 and cfg.conv_channels == 11520
    assert twin().period == ("linear_attention", "linear_attention", "full_attention") and twin().kv_heads_stored == 8
    with pytest.raises(ValueError, match="no rotary"):
        twin(rope_theta=10000.0)
    with pytest.raises(ValueError, match="do not name 6 layers"):
        twin(layer_types=["linear_attention"] * 5)
    with pytest.raises(ValueError, match="K/V heads"):
        twin(num_key_value_heads=2)


def test_prefill_then_decode_steps_give_the_references_logits_at_every_position(served):
    """Tolerance 2e-4 of a position's logits in relative L2: both sides are
    float32 on the CPU; the program's prefill runs the chunkwise form and its
    sums run in another order than the token recurrence's; every fault below
    reads above 1e-2."""
    cfg, params, got, fed, _, _ = served
    want = reference_logits(params, fed, len(PROMPT))
    assert got.shape == want.shape == (STEPS + 1, cfg.vocab_size)
    assert rel_err(got, want) < 2e-4


FAULTS = {
    "beta_not_doubled": "def strength(b, hy):\n    return jax.nn.sigmoid(b)\n",
    "no_decay": "def decay(a, a_log, dt_bias):\n    return jnp.zeros_like(a)\n",
    "no_short_conv": "def short_conv(u, w):\n    return silu(u)\n",
    "no_recurrent_mixer": "def linear_mixer(x, params, ll, hy, precision):\n    return jnp.zeros_like(x)\n",
    "rotary_on_full_layers": "def rotate(x, positions):\n    from benchmarks.reference.longcat import rope\n"
                             "    return rope(x, positions, 10000.0)\n",
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_reference_with_a_planted_fault_is_far_from_the_program(served, fault):
    """What `correct` has to catch (``benchmarks/tests/test_olmo_hybrid.py``
    plants the same in the cell's twin): each reads a hundred times the sound
    comparison's 2e-4."""
    import types

    cfg, params, got, fed, _, _ = served
    faulty = types.ModuleType("faulty")
    exec(compile(open(R.__file__).read() + "\n\n" + FAULTS[fault], R.__file__, "exec"), faulty.__dict__)
    assert rel_err(got, reference_logits(params, fed, len(PROMPT), module=faulty)) > 2e-2


# -- (b) what a state row holds ------------------------------------------------------


def test_a_prompt_shorter_than_its_bucket_leaves_the_state_and_window_of_an_exact_length_pass():
    cfg = twin()
    params = weights(cfg)
    rows = {}
    for bucket in (len(PROMPT), 32, 64):  # 21 tokens: the exact length, padded by 11, by 43 (a whole chunk of padding)
        alloc = BlockAllocator(BLOCKS, BLOCK, state_rows=ROWS)
        logits, pool, table = prefill_into(cfg, params, fresh_pool(cfg), alloc, PROMPT, bucket=bucket)
        rows[bucket] = (logits, *(np.asarray(pool[k][:, table.state_row]) for k in ("state", "conv", "state_pos")))
    for bucket in (32, 64):  # one chunk of 21 against one of 32 and one of 64: the same sums in another order
        for a, b in zip(rows[len(PROMPT)], rows[bucket]):
            np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)
    assert (rows[32][3] == len(PROMPT)).all() and np.abs(rows[32][1]).max() > 0.01
    # the window is the last K inputs of the real tokens, the oldest first
    k, c = cfg.linear_conv_kernel_dim, cfg.conv_channels
    x = params["embed"][jnp.asarray(PROMPT[-k:])]
    np.testing.assert_allclose(rows[32][2][0].reshape(k, c), np.asarray(x @ params["gdn_qkv"][0]), atol=1e-5)


def test_the_same_decode_step_dispatched_twice_leaves_the_pool_bit_for_bit(served):
    """The benchmark's replay calls ``decode_step`` and then
    ``decode_step_greedy`` on the same arguments. The second call finds
    ``state_pos`` already at position + 1 and reads its outputs from the stored
    state and window: the same logits and tokens, the same pool."""
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
        for name, leaf in kept.items():
            np.testing.assert_array_equal(leaf, np.asarray(pool[name]), err_msg=name)
        assert (kept["state_pos"][:, table.state_row] == table.length).all()
        assert (kept["state_pos"][:, 0] == 0).all() and not kept["state"][:, 0].any()  # the null row


def test_a_state_row_handed_to_a_newcomer_carries_nothing_of_its_last_owner(served):
    cfg, params, got, fed, pool, table = served
    row = table.state_row
    assert np.abs(np.asarray(pool["state"][:, row])).max() > 0.01
    alloc = table.allocator
    table.release()
    other = np.random.default_rng(5).integers(1, 255, 9).tolist()
    fns = programs(cfg)
    first, pool, again = prefill_into(cfg, params, pool, alloc, other, fns=fns)
    assert again.state_row == row  # LIFO: the newcomer gets the row just freed
    clean, _, _ = prefill_into(cfg, params, fresh_pool(cfg), BlockAllocator(BLOCKS, BLOCK, state_rows=ROWS), other, fns=fns)
    np.testing.assert_array_equal(first, clean)
    tk, ps, bt, ac = step_args(again, int(first.argmax()))
    logits, pool = fns[1](params, tk, ps, bt, pool, ac)
    want = reference_logits(params, other + [int(first.argmax())], len(other), steps=1)
    assert rel_err(np.stack([first, np.asarray(logits[1])]), want) < 2e-4


# -- (c) the engine ------------------------------------------------------------------


def test_the_engine_serves_twice_its_slots_with_each_request_as_if_alone():
    engine = dict(block_size=BLOCK, num_blocks=BLOCKS, max_batch=2, max_blocks_per_seq=MAX_BLOCKS)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 255, n).tolist() for n in (5, 13, 9, 21)]
    server = LLMServer(TWIN, engine, weight_seed=4)
    try:
        eng = server._engine
        stats = server.kv_stats()
        assert (stats["state_rows_total"], stats["state_rows_used"]) == (2, 0)
        assert stats["state_bytes"] == M.paged_state_bytes(eng.model_cfg) == 4 * (8 * 4 * 16 * 4 + 4 * 128 * 4 + 4)
        assert eng.max_context == (MAX_BLOCKS - 1) * BLOCK and eng._pool["state"].shape[1] == 3
        streams = [server.generate(p, max_new_tokens=10) for p in prompts]  # four requests on two slots
        together = [list(s) for s in streams]
        alone = [list(server.generate(p, max_new_tokens=10)) for p in prompts]
        assert together == alone and all(len(t) == 10 for t in together)
        stats = server.kv_stats()
        assert stats["state_rows_used"] == 0 and stats["blocks_free"] == stats["blocks_total"]
        assert server.loop_stats()["state_rows_total"] == 2
        # and they are the model's tokens: the reference's argmax over what was fed
        params = {**eng.params, "hyper": {
            "layer_types": np.asarray([t == "linear_attention" for t in TWIN["layer_types"]], np.int32),
            "num_attention_heads": 4, "rms_norm_eps": 1e-6, "allow_neg_eigval": 1}}
        seq = np.zeros((64,), np.int32)
        fed = prompts[3] + together[3]
        seq[: len(fed)] = fed
        want = np.asarray(R.logits_at(params, seq, np.arange(len(prompts[3]) - 1, len(fed) - 1), "f32")).argmax(-1)
        assert want.tolist() == together[3]
    finally:
        server._engine.shutdown()
