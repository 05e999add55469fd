"""Granite 4.0-H's language model on its tiny twin (CPU, float32): the paged
programs (a section of a whole period, ten layers a call with attention at the
sixth, and a rest of two; a state row a sequence with the nine Mamba layers'
states and windows beside a flat pool of the attention layer's rows; routing
counts) against the one plain reference
(``benchmarks/reference/granite_hybrid.py``), with a prompt past one chunk of
the SSD form and shorter than its bucket; the faults the comparison has to
catch; the shares of the expert layer with the shared expert counted once; a
decode step dispatched twice; a state row's second owner; the update kernel's
path in interpret mode; and the engine end to end."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import dataclasses  # noqa: E402
import types  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks.families import granite_hybrid as F  # noqa: E402
from benchmarks.reference import granite_hybrid as R  # noqa: E402
from ray_tpu.models import granite_hybrid as M, moe, paged  # noqa: E402
from ray_tpu.serve.llm.deployment import LLMServer, _resolve_model_cfg  # noqa: E402
from ray_tpu.serve.llm.kv_cache import BlockAllocator, BlockTable  # noqa: E402

# twelve layers: a whole period (five Mamba layers, attention, four Mamba layers) and two Mamba layers of the next; four
# query heads on two K/V heads of 16; four Mamba heads of 32 channels (a quarter of a lane tile) in one group, a state of
# 64 a channel, chunks of 8 positions; eight experts, three a token, four held from expert 2; every multiplier the
# published one
TWIN = dict(
    kind="granite_hybrid", vocab_size=256, hidden_size=64, intermediate_size=32, shared_intermediate_size=48,
    num_hidden_layers=12, num_attention_heads=4, num_key_value_heads=2, num_local_experts=8, num_experts_per_tok=3,
    experts_held=4, expert_offset=2, max_position_embeddings=256, mamba_d_state=64, mamba_d_head=32, mamba_n_heads=4,
    mamba_n_groups=1, mamba_d_conv=4, mamba_expand=2, mamba_chunk_size=8, dtype="float32",
)
BLOCK, BLOCKS, MAX_BLOCKS, ROWS, BUCKET = 4, 64, 17, 3, 32  # 16 columns of blocks and the state row's
STEPS = 12
PROMPT = np.random.default_rng(0).integers(1, 255, 21).tolist()  # 21 positions: two chunks of 8 and five of a third, in four


def twin(**over):
    return _resolve_model_cfg({**TWIN, **over})


def model_dict(cfg, **over):
    """The twin as the family's ``model_kwargs`` gives it (what the family's
    functions take)."""
    keys = [k for k in F.PUBLISHED if k != "dtype"] + ["num_local_experts", "experts_held", "expert_offset"]
    return {**{k: getattr(cfg, k) for k in keys}, "dtype": "float32", **over}


def weights(seed=0, **over):
    """The family's seeded weights (the benchmark's recipe) with the ``hyper``
    entry the reference reads; the program ignores it."""
    model = model_dict(twin(), **over)
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
    cfg = M.GraniteHybridConfig()
    mixer = cfg.mamba
    assert (cfg.head_dim, cfg.kv_row, mixer.d_ssm, mixer.conv_dim, mixer.in_dim) == (128, 1024, 8192, 8448, 16768)
    assert (cfg.n_attention, cfg.n_mamba, cfg.n_expert_layers, cfg.experts_held) == (4, 36, 40, 72)
    assert [i for i, kind in enumerate(cfg.layer_types) if kind == "attention"] == [5, 15, 25, 35]
    count = F.weight_count(model_dict(cfg))
    assert count["ssm_mixer"] == 4096 * 16768 + 4 * 8448 + 8448 + 3 * 128 + 8192 + 8192 * 4096 == 102_286_976  # 102.29 M
    assert (count["attention"], count["shared"], count["router"], count["expert"]) == (
        4096 * 6144 + 4096 * 4096, 3 * 4096 * 1536, 4096 * 72, 3 * 4096 * 768)  # 41.94 M, 18.87 M, 0.29 M, 9.44 M
    cut = dataclasses.replace(cfg, num_hidden_layers=10, layer_types=None, experts_held=36, vocab_size=50176)
    count = F.weight_count(model_dict(cut))
    assert count["held"] == 10 * 36 * 9_437_184 and count["head"] == 50176 * 4096 + 4096
    assert count["total"] + count["held"] == 4_757_211_776  # 9.51 GB of bfloat16
    shapes = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cut))
    assert sum(int(np.prod(s.shape)) for s in shapes.values()) == count["total"] + count["held"]
    assert M.paged_block_bytes(cut, 16) == 16 * 2 * 1024 * 2  # K and V of 8 heads of 128, bfloat16, the one attention layer
    assert M.paged_state_bytes(cut) == 9 * (128 * 8192 * 4 + 4 * 8448 * 2 + 4) == 38_357_028  # 38.36 MB a sequence
    pool = jax.eval_shape(lambda: M.init_paged_pool(cut, 6145, 16, 49))
    assert pool["kv"].shape == (1, 2, 6145 * 16 * 8, 128) and pool["state"].shape == (9, 49, 128, 8192)
    assert pool["conv"].shape == (9, 49, 4 * 8448) and pool["state_pos"].shape == (9, 49) and pool["moe_counts"].shape == (len(moe.COUNTS),)
    for refused in (dict(position_embedding_type="rope"), dict(rope_scaling={"type": "yarn"}), dict(attention_bias=True),
                    dict(mamba_proj_bias=True), dict(mamba_conv_bias=False), dict(tie_word_embeddings=False),
                    dict(mamba_n_groups=3), dict(mamba_expand=4), dict(normalization_function="layernorm"),
                    dict(layer_types=["attention"] * 12), dict(expert_offset=6), dict(num_experts_per_tok=0)):
        with pytest.raises(ValueError):
            twin(**refused)


def test_prefill_then_decode_steps_give_the_references_logits_past_a_chunk_and_the_attention_layer(served):
    """Tolerance 1e-4 of a position's logits in relative L2: both sides are
    float32 on the CPU, their sums in another order (the SSD form's matrix
    products against the token walk, the grouped matmuls against the loop over
    experts, the fused projections); the state kept in bfloat16 reads above
    5e-4 over these 13 positions and every other fault above 2e-2. The prompt's
    21 positions lie in a bucket of 32: two whole chunks of 8, a third of five
    tokens and three padded positions, a fourth of padding alone; twelve layers
    are a section of ten (attention its sixth) and a rest of two."""
    cfg, params, got, fed, _, _ = served
    want = reference_logits(params, fed, len(PROMPT))
    assert got.shape == want.shape == (STEPS + 1, cfg.vocab_size)
    assert rel_err(got, want) < 1e-4, rel_err(got, want)


FAULTS = {
    "weights_not_renormalised_over_the_chosen": (
        "def chosen_weights(logits, chosen):\n"
        "    return jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), chosen, axis=-1)\n"),
    "residual_multiplier_left_off_the_expert_branch": (
        "def block(x, params, li, hy, precision):\n"
        "    eps, m_r = hy['rms_norm_eps'], m(hy, 'residual_multiplier')\n"
        "    h = x + m_r * mix(rms_norm(x, params['in_norm'][li], eps), params, li, hy, precision)\n"
        "    return h + moe(rms_norm(h, params['post_norm'][li], eps), params, li, hy, precision)\n"),
    "inverse_root_of_the_head_in_place_of_attention_multiplier": "def score_scale(hy, d):\n    return d ** -0.5\n",
    "a_rotary_applied": (
        "def positioned(q, k, hy):\n"
        "    from benchmarks.reference.exaone_moe import rope\n"
        "    at = jnp.arange(q.shape[0])\n"
        "    return rope(q, at, 10000.0), rope(k, at, 10000.0)\n"),
    "logits_scaling_multiplied": "def logits_scaled(logits, hy):\n    return logits * m(hy, 'logits_scaling')\n",
    "embedding_multiplier_left_out": "def m(hy, name, m=m):\n    return 1.0 if name == 'embedding_multiplier' else m(hy, name)\n",
    "norm_ahead_of_the_gate": ("def gated_norm(y, z, w, groups, eps):\n"
                               "    s = y.shape[0]\n"
                               "    g = y.reshape(s, groups, -1)\n"
                               "    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + eps)\n"
                               "    return g.reshape(s, -1) * w.astype(jnp.float32) * silu(z)\n"),
    "shared_expert_left_out": "def shared_part(u, params, li, precision):\n    return jnp.zeros_like(u)\n",
    "skip_d_left_out": "def skip(d, x):\n    return jnp.zeros_like(x)\n",
    # ``reduce_precision``: a convert there and back is what the TPU's compiler removes (excess precision), a fault unseen
    "state_in_bfloat16": "def kept(state):\n    return jax.lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)\n",
}


def faulty_reference(fault):
    faulty = types.ModuleType("faulty")
    exec(compile(open(R.__file__).read() + "\n\n" + FAULTS[fault], R.__file__, "exec"), faulty.__dict__)
    return faulty


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_reference_with_a_planted_fault_is_far_from_the_program(served, fault):
    """What `correct` has to catch (``benchmarks/tests/test_granite_hybrid.py``
    plants the same in the cell's twin)."""
    cfg, params, got, fed, _, _ = served
    err = rel_err(got, reference_logits(params, fed, len(PROMPT), module=faulty_reference(fault)))
    assert err > (5e-4 if "bfloat16" in fault else 2e-2), err


# -- (b) the expert layer's shares ------------------------------------------------------


@pytest.mark.parametrize("chips,held", [(2, 4), (4, 2)])
def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer_with_the_shared_expert_once(chips, held):
    """``chips`` chips of ``held`` of the 8 experts each (``expert_offset``):
    their routed parts, with what every chip computes alike (the shared expert)
    counted once, add up to the uncut reference's layer."""
    whole = weights(experts_held=8, expert_offset=0)
    hy = R.hyper(whole)
    u = jax.random.normal(jax.random.PRNGKey(3), (24, 64), jnp.float32)
    want = np.asarray(R.moe(u, whole, 7, hy, "f32"))
    shared = np.asarray(R.shared_part(u, whole, 7, "f32"))
    own = lambda name: whole[name][7]  # noqa: E731
    total = shared.copy()
    for offset in range(0, 8, held):
        cfg = twin(experts_held=held, expert_offset=offset)
        share = {name: whole[name][:, offset:offset + held] for name in ("e_gate", "e_up", "e_down")}
        y, counts = M._expert_ffn(cfg, own, share, u, 7, None)
        total += np.asarray(y) - shared
        assert int(counts[0]) + int(counts[2]) == 24 * 3 and int(counts[1]) == 0  # held and absent: every (token, choice) row
    assert chips * held == 8
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-4)


# -- (c) what a state row holds ------------------------------------------------------


def test_a_prompt_shorter_than_its_bucket_leaves_the_rows_of_an_exact_length_pass():
    cfg, params = twin(), weights()
    rows = {}
    for bucket in (24, 32, 64):  # 21 tokens in 24, 32 and 64 positions: three, four and eight chunks
        alloc = BlockAllocator(BLOCKS, BLOCK, state_rows=ROWS)
        logits, pool, table = prefill_into(cfg, params, fresh_pool(cfg), alloc, PROMPT, bucket=bucket)
        rows[bucket] = (logits, *(np.asarray(pool[k][:, table.state_row]) for k in ("state", "conv", "state_pos")))
    for bucket in (32, 64):
        for a, b in zip(rows[24], rows[bucket]):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)
    assert rows[32][1].shape[0] == 11 and (rows[32][3] == len(PROMPT)).all() and np.abs(rows[32][1]).max() > 1e-4


def test_the_same_decode_step_dispatched_twice_leaves_the_pool_bit_for_bit(served):
    """The benchmark's replay calls ``decode_step`` and then
    ``decode_step_greedy`` on the same arguments. The second call finds
    ``state_pos`` already at position + 1 and reads its outputs from the stored
    state and window; the K/V row is written again, the same: the same logits
    and tokens, the same pool (the routing counts aside, which count both)."""
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
            if name == "moe_counts":
                assert (now[[0, 2]] - leaf[[0, 2]]).sum() == 2 * 12 * 3  # two more steps of twelve layers, three choices a token
                continue
            if name == "kv":  # the null block's rows take every inactive slot's writes
                leaf, now = leaf[:, :, BLOCK * G:], now[:, :, BLOCK * G:]
            np.testing.assert_array_equal(leaf, now, err_msg=name)
        assert (kept["state_pos"][:, table.state_row] == table.length).all()
        assert (kept["state_pos"][:, 0] == 0).all() and not kept["state"][:, 0].any()  # the null row


def test_a_decode_step_with_the_update_kernel_in_it_is_the_step_in_jax_numpy(monkeypatch):
    """The state's update on the path it takes on a TPU (``selective_scan_update``
    over the pool in place, decays given a channel: a head of 32 channels is a
    quarter of the kernel's lane tile; here in interpret mode) against
    ``ssm_step``: the same logits and the same pool."""
    from ray_tpu.ops import selective_scan as S

    cfg, params = twin(), weights()
    plain, _, pool, _ = run_paged(cfg, params, PROMPT, steps=6)
    monkeypatch.setattr(S, "can_use_selective_scan_kernel", lambda *_: True)
    update, calls = S.selective_scan_update, []
    monkeypatch.setattr(S, "selective_scan_update", lambda *a, **kw: calls.append(1) or update(*a, **kw, interpret=True))
    kernel, _, kernel_pool, _ = run_paged(cfg, params, PROMPT, steps=6)
    assert len(calls) == 11  # the decode program's eleven Mamba layers, traced once: nine in the period's body, two in the rest
    np.testing.assert_allclose(kernel, plain, atol=5e-5, rtol=5e-5)
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


# -- (d) the engine ------------------------------------------------------------------


def test_the_engine_serves_twice_its_slots_with_each_request_as_if_alone():
    """Four requests on two slots: state rows and blocks handed out and back,
    the ``llm_moe`` counts of a kind that holds half its experts, and the
    model's own tokens (the reference's argmax over what was fed). Telemetry's
    buffer is stood in for (no cluster is connected here), so the loop keeps
    its records."""

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
        # eleven Mamba layers: a state of 64 x 128 float32, a window of 4 x (128 + 2 x 64) float32, a position count
        assert stats["state_bytes"] == M.paged_state_bytes(eng.model_cfg) == 11 * (64 * 128 * 4 + 4 * 256 * 4 + 4)
        assert stats["bytes_per_block"] == 2 * BLOCK * 32 * 4  # the one attention layer's K and V of two heads of 16
        assert eng.max_context == (MAX_BLOCKS - 1) * BLOCK and eng._moe_layers == 12
        assert eng._pool["state"].shape[:2] == (11, 3) and eng._pool["kv"].shape == (1, 2, BLOCKS * BLOCK * 2, 16)
        streams = [server.generate(p, max_new_tokens=10) for p in prompts]  # four requests on two slots
        together = [list(s) for s in streams]
        alone = [list(server.generate(p, max_new_tokens=10)) for p in prompts]
        assert together == alone and all(len(t) == 10 for t in together)
        stats = server.kv_stats()
        assert stats["state_rows_used"] == 0 and stats["blocks_free"] == stats["blocks_total"]  # rows and blocks are back
        eng._moe_copy = (M.routing_counts(eng._pool), eng.decode_steps)
        eng._fold_routing_counts()
        loop = server.loop_stats(records=4096)
        live = [r for r in (dict(zip(loop["fields"], r)) for r in loop["records"]) if r["live"]]
        assert live and all(r["kv_blocks"] > 0 for r in live)
        newest = loop["moe"]
        assert newest["layers"] == 12 and newest["held"] + newest["absent"] == sum(r["live"] for r in live) * 12 * 3
        assert 0 < newest["touched"] <= newest["held"] and newest["zero"] == 0 < newest["absent"]
        # and they are the model's tokens: the reference's argmax over what was fed
        model = model_dict(eng.model_cfg)
        hyper = {**{k: np.int32(model[k]) for k in F.HYPER_INT}, **{k: np.float32(model[k]) for k in F.HYPER_FLOAT}}
        seq = np.zeros((64,), np.int32)
        fed = prompts[3] + together[3]
        seq[: len(fed)] = fed
        want = np.asarray(R.logits_at({**eng.params, "hyper": hyper}, seq, np.arange(len(prompts[3]) - 1, len(fed) - 1),
                                      "f32")).argmax(-1)
        assert want.tolist() == together[3]
    finally:
        server._engine.shutdown()
