"""Phi-4-mini-flash's language model on its tiny twin (CPU, float32): the paged
programs (three sections of a period of two; a state row with rings and
recurrent states; one shared cache that the cross layers read; the memory ``m``
in the scans' carry; a prefill whose last section runs one position) against
the one plain reference (``benchmarks/reference/phi4flash.py``) with contexts
past the window, so that a ring wraps in the prefill and again while decoding;
a prefill with the last-position section against the same layers over every
position; a decode step dispatched twice; a state row's second owner; the
faults the comparison has to catch; and the engine end to end."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks.families import phi4flash as F  # noqa: E402
from benchmarks.reference import phi4flash as R  # noqa: E402
from ray_tpu.models import paged, phi4flash as M  # noqa: E402
from ray_tpu.ops import paged_attention as PA  # noqa: E402
from ray_tpu.serve.llm.deployment import LLMServer, _resolve_model_cfg  # noqa: E402
from ray_tpu.serve.llm.kv_cache import BlockAllocator, BlockTable  # noqa: E402

# twelve layers: (ssm, window) x 3, (ssm 6, full 7), (gmu, cross) x 2; a window of 8 positions
TWIN = dict(
    kind="phi4flash", vocab_size=256, hidden_size=64, intermediate_size=96, num_hidden_layers=12,
    num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=256, layer_norm_eps=1e-5,
    sliding_window=8, mb_per_layer=2, tie_word_embeddings=True, mlp_bias=False, lm_head_bias=False,
    ssm_state_size=16, ssm_conv_kernel=4, ssm_expand=2, ssm_dt_rank=4, dtype="float32",
)
BLOCK, BLOCKS, MAX_BLOCKS, ROWS, BUCKET = 4, 64, 17, 3, 32  # 16 columns of blocks and the state row's
STEPS = 12
PROMPT = np.random.default_rng(0).integers(1, 255, 21).tolist()  # 21 positions through a ring of 8: it wraps twice


def twin(**over):
    return _resolve_model_cfg({**TWIN, **over})


def weights(seed=0):
    """The family's seeded weights (the benchmark's recipe) with the ``hyper``
    entry the reference reads; the program ignores it."""
    model = {k: v for k, v in TWIN.items() if k != "kind"}
    return jax.jit(lambda w: F.make_weights(w, model, jnp.float32))(jnp.asarray([seed, 7], jnp.uint32))


def programs(cfg, paged_layer=M.paged_layer):
    return paged.make_paged_fns(paged_layer, cfg, block_size=BLOCK, state_rows=True)


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
    cfg = M.Phi4FlashConfig()
    assert (cfg.n_ssm, cfg.n_window, cfg.n_cross, cfg.n_attention) == (9, 8, 7, 16)
    assert (cfg.d_inner, cfg.ssm_dt_rank, cfg.pair_dim, cfg.q_pairs, cfg.kv_pairs) == (5120, 160, 128, 20, 10)
    assert M.paged_block_bytes(cfg, 16) == 16 * 5120 and M.paged_ring(cfg) == {"rows": 512, "bytes": 8 * 2_621_440}
    assert M.paged_state_bytes(cfg) == 8 * 2_621_440 + 9 * (327_680 + 40_960 + 4)
    assert [R.kind_of(i, 32) for i in (0, 1, 15, 16, 17, 18, 19, 30, 31)] == [
        "ssm", "window", "window", "ssm", "full", "gmu", "cross", "gmu", "cross"]
    with pytest.raises(ValueError, match="multiple of four"):
        twin(num_hidden_layers=10)
    with pytest.raises(ValueError, match="pairs"):
        twin(num_key_value_heads=4)
    with pytest.raises(ValueError, match="ties the embedding"):
        twin(mlp_bias=True)


def test_prefill_then_decode_steps_give_the_references_logits_past_the_window(served):
    """Tolerance 2e-4 of a position's logits in relative L2: both sides are
    float32 on the CPU, their sums in another order (the banded blocks, the
    packed pairs); every fault below reads above 1e-2. The prompt's 21
    positions wrap a ring of 8 twice and the 12 steps wrap it again."""
    cfg, params, got, fed, _, _ = served
    want = reference_logits(params, fed, len(PROMPT))
    assert got.shape == want.shape == (STEPS + 1, cfg.vocab_size)
    assert rel_err(got, want) < 2e-4


FAULTS = {
    "lam_left_out": "def lam_of(vectors, i):\n    return 0.0\n",
    "no_norm_after_the_subtraction": "def sub_norm(o, w, eps):\n    return o\n",
    "window_ignored": "def window_of(hy):\n    return None\n",
    "memory_dropped": "def memory(m):\n    return jnp.ones_like(m)\n",
    "no_skip": "def skip(d, c):\n    return jnp.zeros_like(c)\n",
    # ``reduce_precision``: a convert there and back is what the TPU's compiler removes (excess precision), a fault unseen
    "state_in_bfloat16": "def kept(state):\n    return jax.lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)\n",
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_reference_with_a_planted_fault_is_far_from_the_program(served, fault):
    """What `correct` has to catch (``benchmarks/tests/test_phi4flash.py``
    plants the same in the cell's twin)."""
    import types

    cfg, params, got, fed, _, _ = served
    faulty = types.ModuleType("faulty")
    exec(compile(open(R.__file__).read() + "\n\n" + FAULTS[fault], R.__file__, "exec"), faulty.__dict__)
    assert rel_err(got, reference_logits(params, fed, len(PROMPT), module=faulty)) > (2e-3 if "bfloat16" in fault else 2e-2)


def test_a_prefill_with_the_last_position_section_gives_the_logits_of_every_section_over_all_positions():
    """The same layers with the last section run over every position (its
    fourth entry dropped, and a cross layer attending over the prompt's rows as
    they come): the same logits and the same pool, and the short form is the one
    the programs trace."""
    cfg, params = twin(), weights()

    def every_position(cfg, params, step):
        carried = M.paged_layer(cfg, params, step)
        return paged.Carried([section[:3] for section in carried.sections], carried.leaf)

    short, pool_short, _ = prefill_into(cfg, params, fresh_pool(cfg), BlockAllocator(BLOCKS, BLOCK, state_rows=ROWS), PROMPT)
    fns = programs(cfg, every_position)
    whole, pool_whole, _ = prefill_into(cfg, params, fresh_pool(cfg), BlockAllocator(BLOCKS, BLOCK, state_rows=ROWS), PROMPT,
                                        fns=fns)
    np.testing.assert_allclose(short, whole, atol=2e-5, rtol=2e-5)
    for name in pool_short:
        np.testing.assert_array_equal(np.asarray(pool_short[name]), np.asarray(pool_whole[name]), err_msg=name)

    def mlp_rows(fn):  # the rows the last section's MLPs multiply: (1, F) against (BUCKET, F)
        jaxpr = str(jax.make_jaxpr(fn)(params, jnp.zeros((1, BUCKET), jnp.int32), jnp.zeros((1, MAX_BLOCKS), jnp.int32),
                                       fresh_pool(cfg), jnp.int32(5)))
        return jaxpr.count(f"f32[1,1,{cfg.intermediate_size}]"), jaxpr.count(f"f32[1,{BUCKET},{cfg.intermediate_size}]")

    one, every = mlp_rows(programs(cfg)[0])
    assert one > 0 and every > 0 and mlp_rows(fns[0])[0] == 0


# -- (b) what a state row holds ------------------------------------------------------


def test_a_prompt_shorter_than_its_bucket_leaves_the_rows_of_an_exact_length_pass():
    cfg, params = twin(), weights()
    rows = {}
    for bucket in (len(PROMPT) + 3, 32, 64):  # 21 tokens in 24, 32 and 64 positions
        alloc = BlockAllocator(BLOCKS, BLOCK, state_rows=ROWS)
        logits, pool, table = prefill_into(cfg, params, fresh_pool(cfg), alloc, PROMPT, bucket=bucket)
        rows[bucket] = (logits, *(np.asarray(pool[k][:, table.state_row])
                                  for k in ("state", "conv", "state_pos", "ring_k", "ring_v")))
    for bucket in (32, 64):
        for a, b in zip(rows[24], rows[bucket]):
            np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)
    assert (rows[32][3] == len(PROMPT)).all() and np.abs(rows[32][1]).max() > 1e-4
    # a ring holds the last 8 positions' rows, position p at p % 8 (13..20 at 5, 6, 7, 0, 1, 2, 3, 4): every row written
    ring = rows[32][4].reshape(cfg.n_window, cfg.sliding_window, cfg.kv_pairs * cfg.pair_dim)
    assert np.abs(ring).max(axis=-1).all()


def test_the_same_decode_step_dispatched_twice_leaves_the_pool_bit_for_bit(served):
    """The benchmark's replay calls ``decode_step`` and then
    ``decode_step_greedy`` on the same arguments. The second call finds
    ``state_pos`` already at position + 1 and reads its outputs from the stored
    state and window; a ring's and the shared cache's rows are written again,
    the same: the same logits and tokens, the same pool."""
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
            if name not in ("kv", "ring_k", "ring_v"):  # the null block's and the null row's rows take every inactive slot's writes
                np.testing.assert_array_equal(leaf, np.asarray(pool[name]), err_msg=name)
        row = table.state_row
        for name in ("ring_k", "ring_v"):
            np.testing.assert_array_equal(kept[name][:, row], np.asarray(pool[name])[:, row], err_msg=name)
        assert (kept["state_pos"][:, row] == table.length).all()
        assert (kept["state_pos"][:, 0] == 0).all() and not kept["state"][:, 0].any()  # the null row


def test_a_decode_step_with_the_paged_kernel_in_it_is_the_step_that_scatters_and_gathers(monkeypatch):
    """The decode step on the path it takes on a TPU (the paged kernel writes
    the full layer's row into the shared cache and the cross layers read it
    through the form without rows; here in interpret mode) against the path it
    takes elsewhere (``_write_spans`` and the gathered table): eight steps from
    position 21 through two block boundaries, an inactive slot either side."""
    cfg = twin(num_attention_heads=8, num_key_value_heads=4)  # two K/V pairs: a block of 4 positions is a whole float32 tile
    params = M.init_params(jax.random.PRNGKey(3), cfg)
    G, steps = cfg.kv_pairs, 8
    _, fresh, _ = prefill_into(cfg, params, fresh_pool(cfg), BlockAllocator(BLOCKS, BLOCK, state_rows=ROWS), PROMPT)
    gathered, _, pool, table = run_paged(cfg, params, PROMPT, steps=steps)
    monkeypatch.setattr(M, "can_use_paged_kernel", lambda *_: True)
    traced = []
    monkeypatch.setattr(M, "paged_decode_attention", lambda *a, **kw: traced.append("new_k" in kw) or PA.paged_decode_attention(
        *a, **kw, interpret=True))
    kernel, _, kernel_pool, kernel_table = run_paged(cfg, params, PROMPT, steps=steps)
    # the prefill's cross layers on its last position; the decode step's full layer, with rows, and its cross layers
    assert traced == [False, True, False]
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


def test_a_state_row_handed_to_a_newcomer_carries_nothing_of_its_last_owner(served):
    cfg, params, got, fed, pool, table = served
    row = table.state_row
    assert np.abs(np.asarray(pool["state"][:, row])).max() > 1e-4 and np.abs(np.asarray(pool["ring_k"][:, row])).max() > 0.01
    alloc = table.allocator
    table.release()
    other = np.random.default_rng(5).integers(1, 255, 5).tolist()  # shorter than the window: its rings are part empty
    fns = programs(cfg)
    first, pool, again = prefill_into(cfg, params, pool, alloc, other, fns=fns)
    assert again.state_row == row  # LIFO: the newcomer gets the row just freed
    clean, _, _ = prefill_into(cfg, params, fresh_pool(cfg), BlockAllocator(BLOCKS, BLOCK, state_rows=ROWS), other, fns=fns)
    np.testing.assert_array_equal(first, clean)
    got2, fed2 = [first], list(other)
    for _ in range(6):  # through the ring's first wrap: the last owner's rows are behind the mask until overwritten
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
        ring = 3 * 2 * 8 * 1 * 32 * 4  # three window layers, K and V, 8 rows of one pair of 32 float32
        assert stats["ring_bytes"] == M.paged_ring(eng.model_cfg)["bytes"] == ring
        assert stats["state_bytes"] == M.paged_state_bytes(eng.model_cfg) == ring + 4 * (16 * 128 * 4 + 4 * 128 * 4 + 4)
        assert stats["bytes_per_block"] == 2 * BLOCK * 32 * 4  # one layer's K and V
        assert eng.max_context == (MAX_BLOCKS - 1) * BLOCK and eng._pool["ring_k"].shape[:2] == (3, 3)
        streams = [server.generate(p, max_new_tokens=10) for p in prompts]  # four requests on two slots
        together = [list(s) for s in streams]
        alone = [list(server.generate(p, max_new_tokens=10)) for p in prompts]
        assert together == alone and all(len(t) == 10 for t in together)
        stats = server.kv_stats()
        assert stats["state_rows_used"] == 0 and stats["blocks_free"] == stats["blocks_total"]  # rings and states are back
        loop = server.loop_stats()
        assert loop["state_rows_total"] == 2 and loop["ring_bytes"] == ring
        if loop["records"]:  # telemetry on: the step record counts the rings' live rows
            recs = [dict(zip(loop["fields"], r)) for r in loop["records"]]
            assert all(0 < r["ring_rows"] <= 8 * r["live"] for r in recs if r["live"])
        # and they are the model's tokens: the reference's argmax over what was fed
        params = {**eng.params, "hyper": {"num_attention_heads": 4, "num_key_value_heads": 2, "sliding_window": 8,
                                          "layer_norm_eps": 1e-5}}
        seq = np.zeros((64,), np.int32)
        fed = prompts[3] + together[3]
        seq[: len(fed)] = fed
        want = np.asarray(R.logits_at(params, seq, np.arange(len(prompts[3]) - 1, len(fed) - 1), "f32")).argmax(-1)
        assert want.tolist() == together[3]
    finally:
        server._engine.shutdown()
