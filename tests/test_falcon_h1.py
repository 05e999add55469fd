"""Falcon-H1's language model on its tiny twin (CPU, float32): the paged
programs (one section of a layer a call; both caches of every layer behind one
block table: K/V rows in the flat pool and a state row with the Mamba-2 state,
the convolution's window and ``state_pos``) against the one plain reference
(``benchmarks/reference/falcon_h1.py``), with a prompt past one chunk of the SSD
form and shorter than its bucket; the faults the comparison has to catch; a
decode step dispatched twice; a state row's second owner; the paged kernel's
path in interpret mode; and the engine end to end."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import dataclasses  # noqa: E402
import types  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks.families import falcon_h1 as F  # noqa: E402
from benchmarks.reference import falcon_h1 as R  # noqa: E402
from ray_tpu.models import falcon_h1 as M, flat_kv, paged  # noqa: E402
from ray_tpu.ops import paged_attention as PA  # noqa: E402
from ray_tpu.serve.llm.deployment import LLMServer, _resolve_model_cfg  # noqa: E402
from ray_tpu.serve.llm.kv_cache import BlockAllocator, BlockTable  # noqa: E402

# three layers; four query heads on two K/V heads of 16; four Mamba heads of 64 channels in two groups (a group a lane
# tile), a state of 64 a channel, chunks of 8 positions; every multiplier the published one
TWIN = dict(
    kind="falcon_h1", vocab_size=256, hidden_size=64, intermediate_size=96, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, max_position_embeddings=256,
    mamba_d_ssm=256, mamba_d_state=64, mamba_d_head=64, mamba_n_heads=4, mamba_n_groups=2, mamba_d_conv=4,
    mamba_chunk_size=8, dtype="float32",
)
BLOCK, BLOCKS, MAX_BLOCKS, ROWS, BUCKET = 4, 64, 17, 3, 32  # 16 columns of blocks and the state row's
STEPS = 12
PROMPT = np.random.default_rng(0).integers(1, 255, 21).tolist()  # 21 positions: two chunks of 8 and five of a third, in four


def twin(**over):
    return _resolve_model_cfg({**TWIN, **over})


def model_dict(cfg):
    """The twin as a configuration file's published keys give it (what the
    family's functions take)."""
    keys = [k for k in F.PUBLISHED if k != "dtype"]
    return {**{k: getattr(cfg, k) for k in keys}, "dtype": "float32"}


def weights(seed=0):
    """The family's seeded weights (the benchmark's recipe) with the ``hyper``
    entry the reference reads; the program ignores it."""
    model = model_dict(twin())
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


def test_the_config_counts_the_published_layer_and_refuses_what_the_program_does_not_run():
    cfg = M.FalconH1Config()
    assert (cfg.kv_row, cfg.bc_dim, cfg.conv_dim, cfg.in_dim) == (512, 512, 5120, 9248)
    count = F.weight_count(model_dict(cfg))
    assert (count["attention"], count["mlp"]) == (31_457_280, 330_301_440)
    assert count["ssm_mixer"] == 5120 * 9248 + 4 * 5120 + 5120 + 3 * 32 + 4096 + 4096 * 5120 == 68_351_072
    assert count["layer"] == 430_120_032 and count["head"] == 261120 * 5120 + 5120  # 430.1 M a layer
    shapes = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), dataclasses.replace(cfg, num_hidden_layers=1)))
    stacked = sum(int(np.prod(s.shape)) for name, s in shapes.items() if name not in paged.UNSTACKED)
    assert stacked == count["layer"]
    assert M.paged_block_bytes(cfg, 16) == 72 * 16 * 2 * 512 * 2  # K and V of 4 heads of 128, bfloat16, every layer
    assert M.paged_state_bytes(cfg) == 72 * (256 * 4096 * 4 + 4 * 5120 * 2 + 4)  # 4,194,304 B of state a layer
    pool = jax.eval_shape(lambda: M.init_paged_pool(dataclasses.replace(cfg, num_hidden_layers=6), 6145, 16, 49))
    assert pool["kv"].shape == (6, 2, 6145 * 16 * 4, 128) and pool["state"].shape == (6, 49, 256, 4096)
    assert pool["conv"].shape == (6, 49, 4 * 5120) and pool["state_pos"].shape == (6, 49)
    for refused in (dict(mamba_rms_norm=False), dict(mamba_norm_before_gate=True), dict(attention_bias=True),
                    dict(mamba_proj_bias=True), dict(mamba_conv_bias=False), dict(rope_scaling={"type": "yarn"}),
                    dict(attn_layer_indices=[0, 2]), dict(tie_word_embeddings=True), dict(mamba_n_groups=3),
                    dict(ssm_multipliers=[1.0, 1.0])):
        with pytest.raises(ValueError):
            twin(**refused)


def test_prefill_then_decode_steps_give_the_references_logits_past_a_chunk(served):
    """Tolerance 5e-5 of a position's logits in relative L2: both sides are
    float32 on the CPU, their sums in another order (the SSD form's matrix
    products against the token walk, the fused projections); the state kept in
    bfloat16 reads 9e-4 over these 13 positions and every other fault above
    0.16. The prompt's 21 positions lie in a bucket of 32: two whole chunks of
    8, a third of five tokens and three padded positions, a fourth of padding
    alone."""
    cfg, params, got, fed, _, _ = served
    want = reference_logits(params, fed, len(PROMPT))
    assert got.shape == want.shape == (STEPS + 1, cfg.vocab_size)
    assert rel_err(got, want) < 5e-5, rel_err(got, want)


FAULTS = {
    "ssm_out_multiplier_dropped": "def m(hy, name, index=None, m=m):\n    return 1.0 if name == 'ssm_out_multiplier' else m(hy, name, index)\n",
    "key_multiplier_dropped": "def m(hy, name, index=None, m=m):\n    return 1.0 if name == 'key_multiplier' else m(hy, name, index)\n",
    "ssm_multiplier_of_dt_dropped": ("def m(hy, name, index=None, m=m):\n"
                                     "    return 1.0 if (name, index) == ('ssm_multipliers', 4) else m(hy, name, index)\n"),
    "mlp_gate_multiplier_dropped": ("def m(hy, name, index=None, m=m):\n"
                                    "    return 1.0 if (name, index) == ('mlp_multipliers', 0) else m(hy, name, index)\n"),
    "embedding_multiplier_dropped": "def m(hy, name, index=None, m=m):\n    return 1.0 if name == 'embedding_multiplier' else m(hy, name, index)\n",
    "b_and_c_of_the_other_group": "def group_of(head, heads, groups):\n    return groups - 1 - head // (heads // groups)\n",
    "norm_ahead_of_the_gate": ("def gated_norm(y, z, w, groups, eps):\n"
                               "    s = y.shape[0]\n"
                               "    g = y.reshape(s, groups, -1)\n"
                               "    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + eps)\n"
                               "    return g.reshape(s, -1) * w.astype(jnp.float32) * silu(z)\n"),
    "norm_over_all_channels": "def gated_norm(y, z, w, groups, eps, gated_norm=gated_norm):\n    return gated_norm(y, z, w, 1, eps)\n",
    "mixers_in_series": ("def mixers(x, u, params, li, hy, precision):\n"
                         "    h = x + m(hy, 'ssm_out_multiplier') * ssm_mixer(m(hy, 'ssm_in_multiplier') * u, params, li, hy, precision)\n"
                         "    u = rms_norm(h, params['in_norm'][li], hy['rms_norm_eps'])\n"
                         "    return h + m(hy, 'attention_out_multiplier') * attention(m(hy, 'attention_in_multiplier') * u, params, li, hy, precision)\n"),
    "ssm_mixer_left_out": ("def mixers(x, u, params, li, hy, precision):\n"
                           "    return x + m(hy, 'attention_out_multiplier') * attention(m(hy, 'attention_in_multiplier') * u, params, li, hy, precision)\n"),
    "attention_left_out": ("def mixers(x, u, params, li, hy, precision):\n"
                           "    return x + m(hy, 'ssm_out_multiplier') * ssm_mixer(m(hy, 'ssm_in_multiplier') * u, params, li, hy, precision)\n"),
    # ``reduce_precision``: a convert there and back is what the TPU's compiler removes (excess precision), a fault unseen
    "state_in_bfloat16": "def kept(state):\n    return jax.lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)\n",
}


def faulty_reference(fault):
    faulty = types.ModuleType("faulty")
    exec(compile(open(R.__file__).read() + "\n\n" + FAULTS[fault], R.__file__, "exec"), faulty.__dict__)
    return faulty


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_reference_with_a_planted_fault_is_far_from_the_program(served, fault):
    """What `correct` has to catch (``benchmarks/tests/test_falcon_h1.py``
    plants the same in the cell's twin)."""
    cfg, params, got, fed, _, _ = served
    err = rel_err(got, reference_logits(params, fed, len(PROMPT), module=faulty_reference(fault)))
    assert err > (5e-4 if "bfloat16" in fault else 2e-2), err


# -- (b) what a state row holds ------------------------------------------------------


def test_a_prompt_shorter_than_its_bucket_leaves_the_rows_of_an_exact_length_pass():
    cfg, params = twin(), weights()
    rows = {}
    for bucket in (24, 32, 64):  # 21 tokens in 24, 32 and 64 positions: three, four and eight chunks
        alloc = BlockAllocator(BLOCKS, BLOCK, state_rows=ROWS)
        logits, pool, table = prefill_into(cfg, params, fresh_pool(cfg), alloc, PROMPT, bucket=bucket)
        rows[bucket] = (logits, *(np.asarray(pool[k][:, table.state_row]) for k in ("state", "conv", "state_pos")))
    for bucket in (32, 64):
        for a, b in zip(rows[24], rows[bucket]):
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)
    assert (rows[32][3] == len(PROMPT)).all() and np.abs(rows[32][1]).max() > 1e-4


def test_the_same_decode_step_dispatched_twice_leaves_the_pool_bit_for_bit(served):
    """The benchmark's replay calls ``decode_step`` and then
    ``decode_step_greedy`` on the same arguments. The second call finds
    ``state_pos`` already at position + 1 and reads its outputs from the stored
    state and window; the K/V row is written again, the same: the same logits
    and tokens, the same pool."""
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
        G = cfg.num_key_value_heads
        for name, leaf in kept.items():
            now = np.asarray(pool[name])
            if name == "kv":  # the null block's rows take every inactive slot's writes
                leaf, now = leaf[:, :, BLOCK * G:], now[:, :, BLOCK * G:]
            np.testing.assert_array_equal(leaf, now, err_msg=name)
        assert (kept["state_pos"][:, table.state_row] == table.length).all()
        assert (kept["state_pos"][:, 0] == 0).all() and not kept["state"][:, 0].any()  # the null row


def test_a_decode_step_with_the_paged_kernel_in_it_is_the_step_that_scatters_and_gathers(monkeypatch):
    """The decode step on the path it takes on a TPU (the paged kernel writes
    every layer's row into the flat pool; here in interpret mode) against the
    path it takes elsewhere (``write_spans`` and the gathered table): eight
    steps from position 21 through two block boundaries, an inactive slot
    either side."""
    cfg = twin(num_attention_heads=8, num_key_value_heads=4, head_dim=8)  # four K/V heads: a block of 4 positions is whole tiles
    params = M.init_params(jax.random.PRNGKey(3), cfg)
    G, steps = cfg.num_key_value_heads, 8
    _, fresh, _ = prefill_into(cfg, params, fresh_pool(cfg), BlockAllocator(BLOCKS, BLOCK, state_rows=ROWS), PROMPT)
    gathered, _, pool, table = run_paged(cfg, params, PROMPT, steps=steps)
    monkeypatch.setattr(flat_kv, "can_use_paged_kernel", lambda *_: True)
    traced = []
    monkeypatch.setattr(flat_kv, "paged_decode_attention", lambda *a, **kw: traced.append("new_k" in kw) or PA.paged_decode_attention(
        *a, **kw, interpret=True))
    kernel, _, kernel_pool, kernel_table = run_paged(cfg, params, PROMPT, steps=steps)
    assert traced == [True]  # the decode step's one layer body, with rows; a prefill scatters
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
    for name in ("state", "conv", "state_pos"):
        np.testing.assert_allclose(np.asarray(kernel_pool[name]), np.asarray(pool[name]), atol=2e-5, rtol=2e-5, err_msg=name)


def test_a_decode_step_with_the_update_kernel_in_it_is_the_step_in_jax_numpy(monkeypatch):
    """The state's update on the path it takes on a TPU (``selective_scan_update``
    over the pool in place, decays given a head, ``B`` and ``C`` a group; here
    in interpret mode) against ``ssm_step``: the same logits and the same pool."""
    from ray_tpu.ops import selective_scan as S

    cfg, params = twin(), weights()
    plain, _, pool, _ = run_paged(cfg, params, PROMPT, steps=6)
    monkeypatch.setattr(S, "can_use_selective_scan_kernel", lambda *_: True)
    update = S.selective_scan_update
    monkeypatch.setattr(S, "selective_scan_update", lambda *a, **kw: update(*a, **kw, interpret=True))
    kernel, _, kernel_pool, _ = run_paged(cfg, params, PROMPT, steps=6)
    np.testing.assert_allclose(kernel, plain, atol=2e-5, rtol=2e-5)
    for name in ("state", "conv", "state_pos"):
        np.testing.assert_allclose(np.asarray(kernel_pool[name]), np.asarray(pool[name]), atol=1e-5, rtol=1e-5, err_msg=name)


def test_a_state_row_handed_to_a_newcomer_carries_nothing_of_its_last_owner(served):
    cfg, params, got, fed, pool, table = served
    row = table.state_row
    assert np.abs(np.asarray(pool["state"][:, row])).max() > 1e-4 and np.abs(np.asarray(pool["conv"][:, row])).max() > 1e-4
    alloc = table.allocator
    table.release()
    other = np.random.default_rng(5).integers(1, 255, 5).tolist()  # shorter than a chunk
    fns = programs(cfg)
    first, pool, again = prefill_into(cfg, params, pool, alloc, other, fns=fns)
    assert again.state_row == row  # LIFO: the newcomer gets the row just freed
    clean, _, _ = prefill_into(cfg, params, fresh_pool(cfg), BlockAllocator(BLOCKS, BLOCK, state_rows=ROWS), other, fns=fns)
    np.testing.assert_array_equal(first, clean)
    got2, fed2 = [first], list(other)
    for _ in range(6):
        tk, ps, bt, ac = step_args(again, int(got2[-1].argmax()))
        fed2.append(int(tk[1]))
        logits, pool = fns[1](params, tk, ps, bt, pool, ac)
        got2.append(np.asarray(logits[1]))
    assert rel_err(np.stack(got2), reference_logits(params, fed2, len(other), steps=6)) < 2e-4


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
        # three layers: a state of 64 x 256 float32, a window of 4 x (256 + 2 x 128) float32, a position count
        assert stats["state_bytes"] == M.paged_state_bytes(eng.model_cfg) == 3 * (64 * 256 * 4 + 4 * 512 * 4 + 4)
        assert stats["bytes_per_block"] == 3 * 2 * BLOCK * 32 * 4  # three layers' K and V of two heads of 16
        assert eng.max_context == (MAX_BLOCKS - 1) * BLOCK and eng._pool["state"].shape[:2] == (3, 3)
        streams = [server.generate(p, max_new_tokens=10) for p in prompts]  # four requests on two slots
        together = [list(s) for s in streams]
        alone = [list(server.generate(p, max_new_tokens=10)) for p in prompts]
        assert together == alone and all(len(t) == 10 for t in together)
        stats = server.kv_stats()
        assert stats["state_rows_used"] == 0 and stats["blocks_free"] == stats["blocks_total"]  # rows and blocks are back
        loop = server.loop_stats()
        assert loop["state_rows_total"] == 2 and loop["state_bytes"] == stats["state_bytes"]
        if loop["records"]:  # telemetry on: the step record counts the dispatched tables' blocks
            recs = [dict(zip(loop["fields"], r)) for r in loop["records"]]
            assert all(r["kv_blocks"] > 0 for r in recs if r["live"])
        # and they are the model's tokens: the reference's argmax over what was fed
        hyper = weights()["hyper"]  # the twin's: what no shape tells
        seq = np.zeros((64,), np.int32)
        fed = prompts[3] + together[3]
        seq[: len(fed)] = fed
        want = np.asarray(R.logits_at({**eng.params, "hyper": hyper}, seq, np.arange(len(prompts[3]) - 1, len(fed) - 1),
                                      "f32")).argmax(-1)
        assert want.tolist() == together[3]
    finally:
        server._engine.shutdown()
