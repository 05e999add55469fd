"""Nemotron-H's language model on its tiny twin (CPU, float32): the paged
programs (one section whose body is the eleven layers ``MEMEMEM*EME``, each a
norm and one part; a state row a sequence with the five mixers' states and
windows beside a flat pool of the one attention layer's rows; routing counts of
the five expert layers; three stacks of weights of different depths) against the
one plain reference (``benchmarks/reference/nemotron_h.py``); the faults the
comparison has to catch; the four shares of the experts, whose latent partial
sums go through ``W_out^lat`` with the shared expert counted once; a token's
result whoever shares its batch; a decode step dispatched twice; two periods as
two calls of one body; the update kernel at eight groups; the published
parameter count; and the engine end to end."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import dataclasses  # noqa: E402
import json  # noqa: E402
import types  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks.families import nemotron_h as F  # noqa: E402
from benchmarks.reference import nemotron_h as R  # noqa: E402
from ray_tpu.models import moe, nemotron_h as M, paged  # noqa: E402
from ray_tpu.serve.llm.deployment import LLMServer, _resolve_model_cfg  # noqa: E402
from ray_tpu.serve.llm.kv_cache import BlockAllocator, BlockTable  # noqa: E402

# eleven layers, the published model's first eleven: five mixers, five expert layers, one attention layer; four query
# heads on two K/V heads of 16; eight mixer heads of 16 channels in eight B/C groups, a state of 64 a channel; a latent
# a quarter of the width; sixteen experts, three a token, four held from expert 8; a shared expert over the whole width
PATTERN = "MEMEMEM*EME"
TWIN = dict(
    kind="nemotron_h", vocab_size=256, hidden_size=64, num_hidden_layers=11, hybrid_override_pattern=PATTERN,
    max_position_embeddings=256, num_attention_heads=4, num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
    mamba_head_dim=16, ssm_state_size=64, n_groups=8, conv_kernel=4, n_routed_experts=16, num_experts_per_tok=3,
    moe_intermediate_size=32, moe_latent_size=16, moe_shared_expert_intermediate_size=48, experts_held=4, expert_offset=8,
    dtype="float32",
)
BLOCK, BLOCKS, MAX_BLOCKS, ROWS, BUCKET = 4, 64, 17, 3, 32  # 16 columns of blocks and the state row's
STEPS = 12
PROMPT = np.random.default_rng(0).integers(1, 255, 21).tolist()
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CONFIG_FILE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks", "configs",
                           "nemotron-3-super-120b-11l.json")


def twin(**over):
    return _resolve_model_cfg({**TWIN, **over})


def model_dict(cfg, **over):
    """The twin as the family's ``model_kwargs`` gives it (what the family's
    functions take)."""
    keys = [k for k in F.PUBLISHED if k != "dtype"] + ["n_routed_experts", "experts_held", "expert_offset"]
    return {**{k: getattr(cfg, k) for k in keys}, "dtype": "float32", **over}


def weights(seed=0, cfg=None, **over):
    """The family's seeded weights (the benchmark's recipe) with the ``hyper``
    entry the reference reads; the program ignores it."""
    model = model_dict(cfg or twin(), **over)
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


# -- (a) the config, and the count it stands for ---------------------------------------


def test_the_config_counts_the_published_layers_and_refuses_what_the_program_does_not_run():
    cfg = M.NemotronHConfig()
    mixer = cfg.mamba
    assert (cfg.head_dim, cfg.kv_row, mixer.d_ssm, mixer.bc_dim, mixer.conv_dim, mixer.in_dim) == (
        128, 256, 8192, 1024, 10240, 18560)
    assert (cfg.n_mamba, cfg.n_expert_layers, cfg.n_attention, cfg.experts_held, cfg.period) == (40, 40, 8, 512, 88)
    assert [i for i, kind in enumerate(cfg.hybrid_override_pattern) if kind == "*"] == [7, 16, 25, 36, 47, 58, 69, 78]
    cut = dataclasses.replace(cfg, num_hidden_layers=11, hybrid_override_pattern=PATTERN, experts_held=128, vocab_size=32768)
    assert M.PATTERN[:11] == PATTERN and (cut.n_mamba, cut.n_expert_layers, cut.n_attention, cut.period) == (5, 5, 1, 11)
    with open(CONFIG_FILE) as f:  # the benchmark's configuration is that cut, through the family's key mapping
        assert _resolve_model_cfg(F.model_kwargs(json.load(f))) == cut
    count = F.weight_count(model_dict(cut))
    shapes = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cut))
    assert sum(int(np.prod(s.shape)) for s in shapes.values()) == count["total"] + count["held"] + count["embed"] == 4_648_163_712
    # the three stacks of weights have different depths
    assert (shapes["norm"].shape[0], shapes["ssm_in"].shape[0], shapes["wqkv"].shape[0], shapes["e_up"].shape[:2]) == (
        11, 5, 1, (5, 128))
    assert shapes["e_up"].shape[2:] == (1024, 2688) and shapes["e_down"].shape[2:] == (2688, 1024) and "e_gate" not in shapes
    assert M.paged_block_bytes(cut, 64) == 64 * 2 * 256 * 2  # K and V of 2 heads of 128, bfloat16, the one attention layer
    assert M.paged_state_bytes(cut) == 5 * (128 * 8192 * 4 + 4 * 10240 * 2 + 4) == 21_381_140  # 21.38 MB a sequence
    pool = jax.eval_shape(lambda: M.init_paged_pool(cut, 1585, 64, 49))
    assert pool["kv"].shape == (1, 2, 1585 * 64 * 2, 128) and pool["state"].shape == (5, 49, 128, 8192)
    assert pool["conv"].shape == (5, 49, 4 * 10240) and pool["state_pos"].shape == (5, 49) and pool["moe_counts"].shape == (len(moe.COUNTS),)
    assert twin(num_hidden_layers=22, hybrid_override_pattern=PATTERN * 2).period == 11
    for refused in (dict(hybrid_override_pattern="MEMEMEM*EM-"), dict(hybrid_override_pattern="MEME"), dict(attention_bias=True),
                    dict(mamba_proj_bias=True), dict(use_conv_bias=False), dict(tie_word_embeddings=True), dict(n_groups=3),
                    dict(num_nextn_predict_layers=1), dict(mlp_hidden_act="silu"), dict(n_group=2), dict(norm_topk_prob=False),
                    dict(sliding_window=128), dict(expert_offset=13), dict(num_experts_per_tok=0), dict(norm_eps=1e-6)):
        with pytest.raises(ValueError):
            twin(**refused)


def test_the_published_keys_count_120_67_billion_parameters_of_which_12_77_a_token():
    """A mixer layer 109.64 M, an attention layer 35.66 M, an expert layer
    54.53 M outside its 512 experts of 5.505 M each, embedding and head 1,073.7
    M; 40 + 40 + 8 layers. From the catalog's row where it is at hand, else
    from the config's defaults (which are that row)."""
    cfg = M.NemotronHConfig()
    if os.path.exists(CATALOG):
        row = next(r for r in map(json.loads, open(CATALOG)) if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
        keys = {k: v for k, v in row["config"].items() if k in {f.name for f in dataclasses.fields(cfg)}}
        published = M.NemotronHConfig(**{**keys, "num_nextn_predict_layers": 0})
        assert published == cfg and row["config"]["num_nextn_predict_layers"] == 1
        assert set(row["config"]) - set(keys) == {"model_type"}  # every published key is the config's, by its name
    w = F.weight_count(model_dict(cfg))
    layer_e = w["router"] + w["latent"] + w["shared"] + 4096
    assert (w["ssm_mixer"] + 4096, w["attention"] + 4096, layer_e, w["expert"]) == (
        109_640_064, 35_655_680, 54_530_560, 5_505_024)
    both = w["embed"] + w["head"]
    assert both == 2 * 131072 * 4096 + 4096
    whole = 40 * (w["ssm_mixer"] + 4096) + 8 * (w["attention"] + 4096) + 40 * (layer_e + 512 * w["expert"]) + both
    active = whole - 40 * (512 - 22) * w["expert"]
    assert round(whole / 1e9, 2) == 120.67 and round(active / 1e9, 2) == 12.77
    assert w["total"] + w["held"] + w["embed"] == whole


# -- (b) the paged programs against the reference's full forward pass ----------


def test_prefill_then_decode_steps_give_the_references_logits(served):
    """Tolerance 1e-4 of a position's logits in relative L2: both sides are
    float32 on the CPU, their sums in another order (the SSD form's matrix
    products against the token walk, the grouped matmuls against the loop over
    experts, the fused projection); the state kept in bfloat16 reads above 5e-4
    over these 13 positions and every other fault above 1e-2. The prompt's 21
    positions lie in a bucket of 32."""
    cfg, params, got, fed, _, _ = served
    want = reference_logits(params, fed, len(PROMPT))
    assert got.shape == want.shape == (STEPS + 1, cfg.vocab_size)
    assert rel_err(got, want) < 1e-4, rel_err(got, want)


FAULTS = {
    "a_silu_gate_given_to_the_experts": "def expert_act(h):\n    return silu(h) * h\n",
    "relu_in_relu_squareds_place": "def expert_act(h):\n    return jnp.maximum(h, 0.0)\n",
    "a_softmax_in_the_sigmoids_place": "def scores(z):\n    return jax.nn.softmax(z, axis=-1)\n",
    "scale_1_in_5s_place": "def routed_scale(hy):\n    return 1.0\n",
    "weights_not_renormalised_over_the_chosen": (
        "def chosen_weights(s, chosen, hy):\n    return routed_scale(hy) * jnp.take_along_axis(s, chosen, axis=-1)\n"),
    "one_bc_group_in_eights_place": "def group_of(head, heads, groups):\n    return 0\n",
    "the_gated_norm_over_all_channels": ("def gated_norm(y, z, w, groups, eps, gated_norm=gated_norm):\n"
                                         "    return gated_norm(y, z, w, 1, eps)\n"),
    "a_rotary_applied": (
        "def positioned(q, k, hy):\n"
        "    from benchmarks.reference.exaone_moe import rope\n"
        "    at = jnp.arange(q.shape[0])\n"
        "    return rope(q, at, 10000.0), rope(k, at, 10000.0)\n"),
    "shared_expert_left_out": "def shared_part(u, params, ei, precision):\n    return jnp.zeros_like(u)\n",
    "routed_experts_left_out": "def routed_part(u, params, ei, hy, precision):\n    return jnp.zeros_like(u)\n",
    # ``reduce_precision``: a convert there and back is what the TPU's compiler removes (excess precision), a fault unseen
    "state_in_bfloat16": "def kept(state):\n    return jax.lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)\n",
}


def faulty_reference(fault):
    faulty = types.ModuleType("faulty")
    exec(compile(open(R.__file__).read() + "\n\n" + FAULTS[fault], R.__file__, "exec"), faulty.__dict__)
    return faulty


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_reference_with_a_planted_fault_is_far_from_the_program(served, fault):
    """What `correct` has to catch (``benchmarks/tests/test_nemotron_h.py``
    plants two of them in the cell's twin; ISSUE 57 lists them for the chip)."""
    cfg, params, got, fed, _, _ = served
    err = rel_err(got, reference_logits(params, fed, len(PROMPT), module=faulty_reference(fault)))
    assert err > (5e-4 if "bfloat16" in fault else 1e-2), err


def test_two_periods_are_two_calls_of_one_body_each_layer_read_by_its_index_among_its_kind():
    """Twenty-two layers, the pattern twice: one section, two calls of a body
    of eleven; the second call's mixers are 5-9, its expert layers 5-9 and its
    attention layer 1 of their stacks."""
    cfg = twin(num_hidden_layers=22, hybrid_override_pattern=PATTERN * 2)
    params = weights(cfg=cfg)
    assert (params["ssm_in"].shape[0], params["e_up"].shape[0], params["wqkv"].shape[0], params["norm"].shape[0]) == (
        10, 10, 2, 22)
    [(_, covered, each)] = M.paged_layer(cfg, params, types.SimpleNamespace(positions=np.zeros((1, 1)), block_size=BLOCK))
    assert (covered, each) == (22, 11)
    got, fed, pool, _ = run_paged(cfg, params, PROMPT, steps=4)
    assert rel_err(got, reference_logits(params, fed, len(PROMPT), steps=4)) < 1e-4
    assert pool["state"].shape[0] == 10 and pool["kv"].shape[0] == 2


# -- (c) the expert layer's shares, and who shares a batch ---------------------------------


@pytest.mark.parametrize("chips,held", [(4, 4), (2, 8)])
def test_the_shares_latent_partial_sums_add_up_through_the_projection_with_the_shared_expert_once(chips, held):
    """``chips`` chips of ``held`` of the 16 experts each (``expert_offset``):
    what each adds to the stream is ``W_out^lat`` times its own experts'
    weighted sum in the latent, plus the shared expert that every chip computes
    alike; the routed parts summed and the shared expert counted once make the
    uncut reference's layer. (A deployment sums the latent partial sums, a
    quarter of the residual's width, and projects once: the projection is
    linear, so the two are one number.)"""
    whole = weights(experts_held=16, expert_offset=0)
    hy = R.hyper(whole)
    u = jax.random.normal(jax.random.PRNGKey(3), (24, 64), jnp.float32)
    want = np.asarray(R.moe(u, whole, 3, hy, "f32"))
    shared = np.asarray(R.shared_part(u, whole, 3, "f32"))
    own = lambda name: whole[name][3]  # noqa: E731
    total, latent = shared.copy(), np.zeros((24, 16), np.float32)
    for offset in range(0, 16, held):
        cfg = twin(experts_held=held, expert_offset=offset)
        share = {name: whole[name][:, offset:offset + held] for name in ("e_up", "e_down")}
        y, counts = M.expert_part(cfg, own, share, u, 3, None)
        total += np.asarray(y) - shared
        assert int(counts[0]) + int(counts[2]) == 24 * 3 and int(counts[1]) == 0  # held and absent: every (token, choice) row
        mixed, _ = moe.expert_layer(
            {**share, "router": own("router"), "router_bias": own("router_bias")}, u, rows=u @ own("lat_in"), layer=3,
            n_routed=16, top_k=3, scale=cfg.routed_scaling_factor, expert_offset=offset, rule=moe.route_sigmoid)
        assert mixed.shape == (24, 16)  # latent-wide: what the chips would exchange
        latent += np.asarray(mixed)
    assert chips * held == 16
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-4)
    np.testing.assert_allclose(latent @ np.asarray(own("lat_out")) + shared, want, atol=2e-5, rtol=2e-4)


def test_a_tokens_result_does_not_depend_on_who_shares_its_batch(served):
    """The same sequence in slot 1 of three with the other slots empty, and in
    slot 0 of three with two other sequences decoding beside it: the same
    logits (the other sequences' rows sort among its own in the grouped
    matmuls, and their states lie in other rows)."""
    cfg, params, got, fed, _, _ = served
    fns = programs(cfg)
    alloc = BlockAllocator(BLOCKS, BLOCK, state_rows=ROWS)
    pool, tables = fresh_pool(cfg), []
    others = [np.random.default_rng(s).integers(1, 255, n).tolist() for s, n in ((1, 9), (2, 17))]
    for prompt in (PROMPT, *others):
        _, pool, table = prefill_into(cfg, params, pool, alloc, prompt, fns=fns)
        tables.append(table)
    rng = np.random.default_rng(9)
    for step in range(4):
        tk, ps = np.zeros((3,), np.int32), np.zeros((3,), np.int32)
        bt, ac = np.zeros((3, MAX_BLOCKS), np.int32), np.ones((3,), bool)
        for slot, table in enumerate(tables):
            tk[slot] = fed[len(PROMPT) + step] if slot == 0 else rng.integers(1, 255)
            ps[slot] = table.length
            table.append_token()
            bt[slot] = table.as_list(MAX_BLOCKS)
        logits, pool = fns[1](params, jnp.asarray(tk), jnp.asarray(ps), jnp.asarray(bt), pool, jnp.asarray(ac))
        np.testing.assert_allclose(np.asarray(logits[0]), got[1 + step], atol=2e-5, rtol=2e-5)


# -- (d) what the pool holds ---------------------------------------------------------------


def test_the_same_decode_step_dispatched_twice_leaves_the_pool_bit_for_bit(served):
    """The benchmark's replay calls ``decode_step`` and then
    ``decode_step_greedy`` on the same arguments. The second call finds
    ``state_pos`` already at position + 1 and reads its outputs from the stored
    state and window; the K/V row is written again, the same: the same logits
    and tokens, the same pool (the routing counts aside, which count both, the
    five expert layers alone)."""
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
                # two more steps of five expert layers, three choices a token
                assert (now[[0, 2]] - leaf[[0, 2]]).sum() == 2 * 5 * 3
                continue
            if name == "kv":  # the null block's rows take every inactive slot's writes
                leaf, now = leaf[:, :, BLOCK * G:], now[:, :, BLOCK * G:]
            np.testing.assert_array_equal(leaf, now, err_msg=name)
        assert (kept["state_pos"][:, table.state_row] == table.length).all()
        assert (kept["state_pos"][:, 0] == 0).all() and not kept["state"][:, 0].any()  # the null row


def test_the_update_kernel_at_eight_groups_of_whole_lane_tiles_is_the_step_in_jax_numpy():
    """``selective_scan_update`` (interpret mode) at eight B/C groups, each a
    lane tile of channels (the published mixer's are eight tiles each), decays
    given a channel: against ``ssm_step``, a row that advances and one that
    does not."""
    from ray_tpu.ops import selective_scan as S

    n, d_in, groups = 16, 1024, 8
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 8))
    pool = jax.random.normal(next(keys), (2, 4, n, d_in), jnp.float32)
    x, dl = jax.random.normal(next(keys), (2, d_in)), jax.nn.softplus(jax.random.normal(next(keys), (2, d_in)))
    bm, cm = jax.random.normal(next(keys), (2, groups, n)), jax.random.normal(next(keys), (2, groups, n))
    decay = jnp.exp(-dl * jnp.exp(jax.random.normal(next(keys), (d_in,))))
    rows, advance = jnp.asarray([2, 3]), jnp.asarray([True, False])
    y, new = S.selective_scan_update(pool, 1, rows, advance, x, dl, bm, cm, decay=decay, interpret=True)
    want_y, want = S.ssm_step(pool[1, rows], x, dl, bm, cm, None, advance, decay=decay)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(new[1, rows]), np.asarray(want), rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(pool[0]))
    assert not np.allclose(np.asarray(want_y), np.asarray(S.ssm_step(pool[1, rows], x, dl, bm[:, :1], cm[:, :1], None, advance,
                                                                      decay=decay)[0]), atol=1e-2)  # one group is another result


# -- (e) the engine ------------------------------------------------------------------


def test_the_engine_serves_twice_its_slots_with_each_request_as_if_alone():
    """Four requests on two slots: state rows and blocks handed out and back,
    the ``llm_moe`` counts of a kind whose expert layers are five of eleven and
    hold a quarter of the experts, and the model's own tokens (the reference's
    argmax over what was fed). Telemetry's buffer is stood in for (no cluster
    is connected here), so the loop keeps its records."""

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
        # five mixers: a state of 64 x 128 float32, a window of 4 x (128 + 2 x 8 x 64) float32, a position count
        assert stats["state_bytes"] == M.paged_state_bytes(eng.model_cfg) == 5 * (64 * 128 * 4 + 4 * 1152 * 4 + 4)
        assert stats["bytes_per_block"] == 2 * BLOCK * 32 * 4  # the one attention layer's K and V of two heads of 16
        assert eng.max_context == (MAX_BLOCKS - 1) * BLOCK and eng._moe_layers == 5
        assert eng._pool["state"].shape[:2] == (5, 3) and eng._pool["kv"].shape == (1, 2, BLOCKS * BLOCK * 2, 16)
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
        assert newest["layers"] == 5 and newest["held"] + newest["absent"] == sum(r["live"] for r in live) * 5 * 3
        assert 0 < newest["touched"] <= newest["held"] and newest["zero"] == 0 < newest["absent"]
        # and they are the model's tokens: the reference's argmax over what was fed
        model = model_dict(eng.model_cfg)
        hyper = {"pattern": np.asarray([ord(c) for c in PATTERN], np.int32), **{k: np.int32(model[k]) for k in F.HYPER_INT},
                 **{k: np.float32(model[k]) for k in F.HYPER_FLOAT}}
        seq = np.zeros((64,), np.int32)
        fed = prompts[3] + together[3]
        seq[: len(fed)] = fed
        want = np.asarray(R.logits_at({**eng.params, "hyper": hyper}, seq, np.arange(len(prompts[3]) - 1, len(fed) - 1),
                                      "f32")).argmax(-1)
        assert want.tolist() == together[3]
    finally:
        server._engine.shutdown()
