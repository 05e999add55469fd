"""Kimi-K2's language model on its tiny twin (CPU, float32): the paged programs
(two sections: a dense layer, then expert layers) against the one plain
reference (``benchmarks/reference/kimi.py``), the expert layer's shares against
the uncut layer, the second routing rule against the reference's general form,
YaRN's frequencies against numbers worked by hand, the faults the comparison
has to catch, the ``peak`` count, and the engine end to end."""

import math
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks.reference import kimi as R  # noqa: E402
from ray_tpu.models import kimi as M, moe, paged, paged_model  # noqa: E402
from ray_tpu.ops.latent_attention import yarn_inv_freq  # noqa: E402
from ray_tpu.serve.llm.deployment import LLMServer, _resolve_model_cfg  # noqa: E402
from ray_tpu.serve.llm.kv_cache import BlockAllocator, BlockTable  # noqa: E402

# one dense layer and two expert layers; YaRN over a context of 16 stretched 4 times, so that the
# prompts below reach past the original context and both ends of the ramp are among the 4 pairs
TWIN_YARN = dict(type="yarn", factor=4, original_max_position_embeddings=16, beta_fast=2, beta_slow=1, mscale=1,
                 mscale_all_dim=1)
TWIN = dict(
    kind="kimi_k2", vocab_size=256, hidden_size=64, intermediate_size=160, moe_intermediate_size=32,
    num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=4, kv_lora_rank=16, q_lora_rank=32,
    qk_rope_head_dim=8, qk_nope_head_dim=8, v_head_dim=8, n_routed_experts=16, num_experts_per_tok=3,
    max_position_embeddings=256, rope_theta=100.0, rope_scaling=TWIN_YARN, dtype="float32",
)
BLOCK, BLOCKS, MAX_BLOCKS = 4, 64, 16
STEPS = 16


def twin(**over):
    return _resolve_model_cfg({**TWIN, **over})


def weights(cfg, seed=0):
    """The model's seeded weights with a choice bias large enough to move
    choices among the twin's 16 experts (the seeded one is sized for 384)."""
    params = M.init_params(jax.random.PRNGKey(seed), cfg)
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(seed + 100), params["router_bias"].shape)
    return {**params, "router_bias": bias}


def for_reference(params, cfg, **over):
    """The program's weights with the ``hyper`` entry the reference reads."""
    ys = cfg.rope_scaling
    hyper = dict(
        expert_offset=cfg.expert_offset, num_experts_per_tok=cfg.num_experts_per_tok, n_group=cfg.n_group,
        topk_group=cfg.topk_group, routed_scaling_factor=cfg.routed_scaling_factor, rms_norm_eps=cfg.rms_norm_eps,
        rope_theta=cfg.rope_theta, **{k: ys[k] for k in ys if k != "type"},
    )
    return {**params, "hyper": {**hyper, **over}}


def run_paged(cfg, params, prompt, steps=STEPS, batch=3, slot=1):
    """Prefill ``prompt``, then ``steps`` greedy decode steps in ``slot`` of a
    batch of ``batch``. Returns (logits of every position fed (steps + 1, V),
    tokens fed, the pool)."""
    prefill, decode, _ = paged.make_paged_fns(M.paged_layer, cfg, block_size=BLOCK)
    pool = M.init_paged_pool(cfg, BLOCKS, BLOCK)
    table = BlockTable(BlockAllocator(BLOCKS, BLOCK))
    table.reserve(len(prompt))
    table.length = len(prompt)
    toks = np.zeros((1, 32), np.int32)
    toks[0, : len(prompt)] = prompt
    logits, pool = prefill(params, jnp.asarray(toks), jnp.asarray([table.as_list(MAX_BLOCKS)], jnp.int32), pool,
                           jnp.int32(len(prompt)))
    got, fed = [np.asarray(logits[0])], list(prompt)
    for _ in range(steps):
        tk, ps = np.zeros((batch,), np.int32), np.zeros((batch,), np.int32)
        bt, ac = np.zeros((batch, MAX_BLOCKS), np.int32), np.zeros((batch,), bool)
        tk[slot], ps[slot], ac[slot] = int(got[-1].argmax()), table.length, True
        table.append_token()
        bt[slot] = table.as_list(MAX_BLOCKS)
        fed.append(int(tk[slot]))
        logits, pool = decode(params, jnp.asarray(tk), jnp.asarray(ps), jnp.asarray(bt), pool, jnp.asarray(ac))
        got.append(np.asarray(logits[slot]))
    return np.stack(got), fed, pool


def reference_logits(ref_params, fed, n_prompt, steps=STEPS):
    seq = np.zeros((64,), np.int32)
    seq[: len(fed)] = fed
    return np.asarray(R.logits_at(ref_params, seq, np.arange(n_prompt - 1, n_prompt + steps), "f32"))


def rel_err(got, want):
    return float((np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)).max())


PROMPT = np.random.default_rng(0).integers(1, 255, 21).tolist()  # past the twin's original context of 16


@pytest.fixture(scope="module")
def served():
    """The uncut twin served through the paged programs, once."""
    cfg = twin()
    params = weights(cfg)
    got, fed, pool = run_paged(cfg, params, PROMPT)
    return cfg, params, got, fed, pool


# -- (a) the paged programs against the reference's full forward pass ----------


def test_prefill_then_decode_steps_give_the_references_logits_at_every_position(served):
    """Tolerance 1e-4 of a position's logits in relative L2: both sides are
    float32 (the CPU's matmuls are exact to float32 rounding), the program's
    attention is absorbed and its experts grouped, so the sums run in another
    order; any fault of substance reads above 1e-3 (the faults below)."""
    cfg, params, got, fed, pool = served
    want = reference_logits(for_reference(params, cfg), fed, len(PROMPT))
    assert got.shape == want.shape == (STEPS + 1, cfg.vocab_size)
    assert rel_err(got, want) < 1e-4
    counts = dict(zip(moe.COUNTS, np.asarray(pool["moe_counts"]).tolist()))
    # decode steps alone are counted: one live row, top-3, the two expert layers, every expert held
    assert counts["held"] == STEPS * 2 * 3 and counts["zero"] == counts["absent"] == 0
    # one row chooses three distinct experts: three touched a layer a step, none with more than a row
    assert counts["touched"] == STEPS * 2 * 3 and counts["peak"] == STEPS * 2


def test_a_share_of_the_experts_gives_the_references_logits_for_the_same_share():
    cfg = twin(experts_held=6, expert_offset=4)
    params = weights(cfg, seed=1)
    got, fed, pool = run_paged(cfg, params, PROMPT)
    assert rel_err(got, reference_logits(for_reference(params, cfg), fed, len(PROMPT))) < 1e-4
    assert np.asarray(pool["moe_counts"])[2] > 0  # some choices went to experts held elsewhere
    whole = reference_logits(for_reference(params, cfg, expert_offset=0), fed, len(PROMPT))
    assert rel_err(got, whole) > 1e-3  # and the offset is not decoration


# -- (b) the shares add up ---------------------------------------------------------


def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """The routed parts that the four shares of a layer give, with the shared
    expert (which every chip computes alike, for its own tokens) counted
    once, equal the uncut reference's layer."""
    cfg = twin()
    params = weights(cfg, seed=2)
    u = jax.random.normal(jax.random.PRNGKey(3), (24, cfg.hidden_size))
    layer = {k: params[k][1] for k in ("router", "router_bias", "e_gate", "e_up", "e_down")}
    kw = dict(n_routed=cfg.n_routed_experts, top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
              rule=moe.route_sigmoid)
    ref = for_reference(params, cfg)
    hy = R.hyper(ref)
    want = np.asarray(R.moe(u, ref, 1, hy, "f32"))  # every expert held, and the shared expert
    shared = np.asarray(R.shared_part(u, ref, 1, "f32"))
    total, rows = np.zeros_like(want), np.zeros(len(moe.COUNTS), np.int64)
    for offset in range(0, cfg.n_routed_experts, 4):
        share = {**layer, **{k: layer[k][offset:offset + 4] for k in ("e_gate", "e_up", "e_down")}}
        y, counts = moe.expert_layer(share, u, expert_offset=offset, **kw)
        total += np.asarray(y)
        rows += np.asarray(counts)
    np.testing.assert_allclose(total + shared, want, atol=2e-5)
    held, zero, absent = rows[:3]
    assert zero == 0 and held == 24 * 3 and absent == 3 * held  # a row is held by one share, absent from three


# -- (c) the second routing rule ------------------------------------------------------


def _router(seed=5, t=200, d=32, n=16):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    u = jax.random.normal(k1, (t, d))
    return u, jax.random.normal(k2, (d, n)) * d ** -0.5 * 1.5, 0.05 * jax.random.normal(k3, (n,))


def test_the_sigmoid_rule_is_the_references_at_one_group_and_the_bias_moves_the_choice_only():
    u, router, bias = _router()
    hy = dict(num_experts_per_tok=3, n_group=1, topk_group=1, routed_scaling_factor=2.827)
    w, chosen = moe.route_sigmoid(u, router, bias, top_k=3, scale=2.827)
    w_ref, chosen_ref = R.route(u, router, bias, hy, "f32")
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(chosen_ref))
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.827, rtol=1e-5)  # renormalised over the chosen, then scaled
    # the bias changes some tokens' chosen sets, and is in no weight: where the set stayed, so did the weights
    w0, chosen0 = moe.route_sigmoid(u, router, jnp.zeros_like(bias), top_k=3, scale=2.827)
    same = np.all(np.sort(np.asarray(chosen), -1) == np.sort(np.asarray(chosen0), -1), axis=-1)
    assert 0.1 < 1 - same.mean() < 0.9
    np.testing.assert_allclose(np.sort(np.asarray(w), -1)[same], np.sort(np.asarray(w0), -1)[same], rtol=1e-6)


def test_the_references_grouped_rule_reduces_to_the_programs_over_the_group_that_stays():
    """``n_group`` 2, ``topk_group`` 1 in the reference: every token's choices
    lie in the half whose two largest ``s + b`` sum higher, and are what the
    program's rule (one group) gives over that half's experts alone."""
    u, router, bias = _router(seed=6)
    hy = dict(num_experts_per_tok=3, n_group=2, topk_group=1, routed_scaling_factor=2.827)
    w_ref, chosen_ref = (np.asarray(x) for x in R.route(u, router, bias, hy, "f32"))
    halves = [moe.route_sigmoid(u, router[:, lo:lo + 8], bias[lo:lo + 8], top_k=3, scale=2.827) for lo in (0, 8)]
    s = np.asarray(jax.nn.sigmoid(u @ router) + bias)
    best = np.stack([np.sort(s[:, lo:lo + 8], -1)[:, -2:].sum(-1) for lo in (0, 8)], -1).argmax(-1)
    assert 0.2 < best.mean() < 0.8  # both halves stay for some tokens
    for t in range(len(best)):
        w, chosen = halves[best[t]]
        np.testing.assert_array_equal(chosen_ref[t], np.asarray(chosen[t]) + 8 * best[t])
        np.testing.assert_allclose(w_ref[t], np.asarray(w[t]), rtol=1e-6)
    ungrouped = np.asarray(moe.route_sigmoid(u, router, bias, top_k=3, scale=2.827)[1])
    assert np.any(np.sort(ungrouped, -1) != np.sort(chosen_ref, -1))  # the groups are not decoration
    with pytest.raises(ValueError, match="one group"):
        twin(n_group=2)


# -- (d) YaRN ---------------------------------------------------------------------------


def test_yarns_frequencies_at_the_published_numbers_by_hand():
    """theta 50000, 64 rotary values, original context 4096, both betas 1: the
    pair that turns once over 4096 positions is 64 ln(4096 / 2 pi) / (2 ln
    50000) = 19.17, so ``low`` 19 and ``high`` 20: pairs 0..19 keep their
    frequency, pairs 20..31 are slowed 32 times, no pair lies in between."""
    dim = 64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(50000))
    assert 19.1 < dim < 19.2
    cfg = M.KimiConfig()
    got = yarn_inv_freq(64, 50000.0, **cfg.rope_scaling)
    plain = 50000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(got[:20], plain[:20], rtol=1e-6)
    np.testing.assert_allclose(got[20:], plain[20:] / 32, rtol=1e-6)
    assert got[19] == pytest.approx(50000 ** (-19 / 32), rel=1e-6)  # 0.001624: turns 1.06 times over 4096 positions
    assert got[20] == pytest.approx(50000 ** (-20 / 32) / 32, rel=1e-6)
    np.testing.assert_allclose(R.inv_freq(64, dict(rope_theta=50000.0, **cfg.rope_scaling)), got, rtol=1e-6)
    m = 0.1 * math.log(32) + 1
    assert m == pytest.approx(1.3466, abs=1e-4) and cfg.att_scale == pytest.approx(m * m / math.sqrt(192))
    # the twin's: a ramp with a pair inside it
    twin_freq = yarn_inv_freq(8, 100.0, **TWIN_YARN)
    np.testing.assert_allclose(twin_freq, R.inv_freq(8, dict(rope_theta=100.0, **TWIN_YARN)), rtol=1e-6)
    assert twin_freq[0] == 1.0 and twin_freq[-1] == pytest.approx(100 ** -0.75 / 4)


# -- (e) what the comparison has to catch -------------------------------------------


def _route(kind):
    def route(u, router, bias, hy, precision):
        z = jnp.einsum("sd,dn->sn", u, router.astype(jnp.float32), precision=R.HIGHEST)
        s = jax.nn.softmax(z, axis=-1) if kind == "softmax_scores" else jax.nn.sigmoid(z)
        _, chosen = jax.lax.top_k(s if kind == "bias_ignored" else s + bias, hy["num_experts_per_tok"])
        w = jnp.take_along_axis(s + bias if kind == "bias_in_the_weights" else s, chosen, axis=-1)
        if kind != "not_renormalised":
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        return hy["routed_scaling_factor"] * w, chosen

    return route


def _dense_everywhere(x, params, li, hy, precision):  # every layer takes the leading layer's MLP
    h = x + R.mla(R.rms_norm(x, params["in_norm"][li], hy["rms_norm_eps"]), params, li, hy, precision)
    return h + R.dense_ffn(R.rms_norm(h, params["post_norm"][li], hy["rms_norm_eps"]), params, 0, precision)


FAULTS = {
    "routed_experts_left_out": dict(patch=("routed_part", lambda u, w, c, params, ei, hy, precision: jnp.zeros_like(u))),
    "shared_expert_left_out": dict(patch=("shared_part", lambda u, params, ei, precision: jnp.zeros_like(u))),
    "dense_layer_everywhere": dict(patch=("block", _dense_everywhere)),
    "bias_ignored": dict(patch=("route", _route("bias_ignored"))),
    "bias_in_the_weights": dict(patch=("route", _route("bias_in_the_weights"))),
    "not_renormalised": dict(patch=("route", _route("not_renormalised"))),
    "softmax_scores": dict(patch=("route", _route("softmax_scores"))),
    "plain_rotary_and_score_scale": dict(hyper={"factor": 1.0}),
    "score_scale_without_m2": dict(hyper={"mscale_all_dim": 0.0}),
    "scaling_factor_left_out": dict(hyper={"routed_scaling_factor": 1.0}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_reference_with_this_fault_is_told_from_the_program(served, fault, monkeypatch):
    cfg, params, got, fed, _ = served
    spec = FAULTS[fault]
    if "patch" in spec:
        monkeypatch.setattr(R, *spec["patch"])
    want = reference_logits(for_reference(params, cfg, **spec.get("hyper", {})), fed, len(PROMPT))
    assert rel_err(got, want) > 1e-3, fault


# -- (f) the peak count -----------------------------------------------------------------


def test_the_peak_count_is_the_rows_of_the_fullest_held_expert():
    d, f, t = 16, 32, 40
    params = moe.init_expert_params(jax.random.PRNGKey(0), d, f, held=3, n_outputs=6)
    # every token's first choice is expert 1 (held); its second is among the rest
    params["router_bias"] = jnp.asarray([0.0, 10.0, 0.0, 0.0, 0.0, 0.0])
    u = jax.random.normal(jax.random.PRNGKey(1), (t, d))
    for rule in (moe.route, moe.route_sigmoid):
        _, counts = jax.jit(lambda rows, rule=rule: moe.expert_layer(
            params, rows, n_routed=6, top_k=2, scale=2.0, rule=rule))(u)
        c = dict(zip(moe.COUNTS, np.asarray(counts).tolist()))
        _, chosen = rule(u, params["router"], params["router_bias"], top_k=2, scale=2.0)
        sizes = np.bincount(np.asarray(chosen).ravel(), minlength=6)[:3]
        assert c["peak"] == sizes.max() == t and c["held"] == sizes.sum() and c["touched"] == (sizes > 0).sum()
        assert c["held"] + c["absent"] == 2 * t and c["zero"] == 0
    # rows that are no tokens count nowhere
    _, counts = moe.expert_layer(params, u, n_routed=6, top_k=2, scale=2.0, live=jnp.arange(t) < 7)
    assert dict(zip(moe.COUNTS, np.asarray(counts).tolist()))["peak"] == 7


# -- (g) the engine end to end ----------------------------------------------------------


def test_the_engine_serves_the_replayed_tokens_and_reports_its_expert_layers():
    server = LLMServer(TWIN, dict(block_size=BLOCK, num_blocks=BLOCKS, max_batch=4, max_blocks_per_seq=MAX_BLOCKS),
                       weight_seed=5)
    try:
        eng = server._engine
        cfg = eng.model_cfg
        assert isinstance(cfg, M.KimiConfig) and paged_model(cfg) is M
        assert (cfg.n_layers, cfg.n_expert_layers, eng._moe_layers) == (3, 2, 2)
        rng = np.random.default_rng(7)
        prompts = [rng.integers(1, 255, n).tolist() for n in (5, 9, 14, 17, 21, 30)]
        streams = [server.generate(p, max_new_tokens=6 + i) for i, p in enumerate(prompts)]  # 6 requests, 4 slots
        served_tokens = [list(s) for s in streams]
        for i, (p, toks) in enumerate(zip(prompts, served_tokens)):
            _, fed, _ = run_paged(cfg, eng.params, p, steps=5 + i, batch=4, slot=0)
            assert toks[:-1] == fed[len(p):], i
        stats = server.kv_stats()
        assert stats["bytes_per_block"] == cfg.num_hidden_layers * BLOCK * 128 * 4 == M.paged_block_bytes(cfg, BLOCK)
        pool = eng._pool["latent"]
        assert pool.shape == (3, BLOCKS, BLOCK, 128) and stats["blocks_free"] == stats["blocks_total"] == BLOCKS - 1
        eng._moe_copy = (M.routing_counts(eng._pool), eng.decode_steps)
        eng._fold_routing_counts()
        total = dict(zip(moe.COUNTS, eng._moe_total))
        rows = sum(5 + i for i in range(6)) * cfg.n_expert_layers * cfg.num_experts_per_tok
        assert total["held"] == rows and total["zero"] == total["absent"] == 0
        assert 0 < total["peak"] <= total["touched"] <= total["held"]
        assert 0 < total["windows"] <= eng.decode_steps * cfg.n_expert_layers  # every expert held: one window a call
        newest = server.loop_stats()["moe"]
        if newest is not None:  # telemetry on: the newest record names every count, and the kind's expert layers
            assert {k: newest[k] for k in moe.COUNTS} == total and newest["layers"] == 2
    finally:
        server._engine.shutdown()


def test_loop_stats_names_the_peak_count_in_the_newest_moe_record():
    """``loop_stats()["moe"]`` is the newest ``llm_moe`` record by field name
    (``looplog.LLM_MOE_FIELDS``): with telemetry's buffer stood in for (no
    cluster is connected here), the engine's record carries ``peak`` between
    ``touched`` and ``layers``."""
    from ray_tpu._private import looplog
    from ray_tpu.serve.llm.engine import EngineConfig, InferenceEngine

    class Buffer:
        def record_loop(self, stem, rec):
            pass

    cfg = twin()
    eng = InferenceEngine(weights(cfg), cfg, EngineConfig(block_size=BLOCK, num_blocks=BLOCKS, max_batch=2,
                                                          max_blocks_per_seq=MAX_BLOCKS), deployment="kimi-moe")
    eng._tel = Buffer()
    try:
        assert eng.submit(PROMPT[:9], max_new_tokens=8).tokens()
        eng._moe_copy = (M.routing_counts(eng._pool), eng.decode_steps)
        eng._fold_routing_counts()
        newest = eng.loop_stats()["moe"]
        assert list(newest) == list(looplog.LLM_MOE_FIELDS)
        assert looplog.LLM_MOE_FIELDS[2:-1] == moe.COUNTS and newest["layers"] == 2
        assert newest["held"] == 7 * 2 * 3 and newest["peak"] == 7 * 2 and newest["touched"] == newest["held"]
        assert newest["windows"] == 7 * 2  # a window a layer a step
        assert '"peak": 14' in looplog.encode(("m", *newest.values()))
    finally:
        eng.shutdown()
