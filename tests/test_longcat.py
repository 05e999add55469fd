"""LongCat-Flash's language model on its tiny twin (CPU, float32): the paged
programs against the one plain reference (``benchmarks/reference/longcat.py``),
the two attention paths against each other, the expert layer's shares against
the uncut layer, the faults the comparison has to catch, and the engine end to
end."""

import dataclasses
import os
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks.reference import longcat as R  # noqa: E402
from ray_tpu.models import longcat as M, moe, paged, paged_model  # noqa: E402
from ray_tpu.ops.latent_attention import (  # noqa: E402
    latent_decode_attention,
    latent_prefill_attention,
    rope_interleaved,
)
from ray_tpu.serve.llm.deployment import LLMServer, _resolve_model_cfg  # noqa: E402
from ray_tpu.serve.llm.kv_cache import BlockAllocator, BlockTable  # noqa: E402

TWIN = dict(
    kind="longcat", vocab_size=256, hidden_size=64, ffn_hidden_size=128, expert_ffn_hidden_size=32, num_layers=2,
    num_attention_heads=4, kv_lora_rank=16, q_lora_rank=32, qk_rope_head_dim=4, qk_nope_head_dim=8, v_head_dim=8,
    n_routed_experts=8, zero_expert_num=4, moe_topk=3, max_position_embeddings=256, dtype="float32",
)
BLOCK, BLOCKS, MAX_BLOCKS = 4, 64, 16
STEPS = 16


def twin(**over):
    return _resolve_model_cfg({**TWIN, **over})


def weights(cfg, seed=0):
    """The model's seeded weights with a choice bias large enough to move
    choices (the seeded one is small beside a chosen ``p``)."""
    params = M.init_params(jax.random.PRNGKey(seed), cfg)
    bias = 0.02 * jax.random.normal(jax.random.PRNGKey(seed + 100), params["router_bias"].shape)
    return {**params, "router_bias": bias}


def for_reference(params, cfg, **over):
    """The program's weights with the ``hyper`` entry the reference reads."""
    hyper = dict(
        n_routed_experts=cfg.n_routed_experts, expert_offset=cfg.expert_offset, moe_topk=cfg.moe_topk,
        routed_scaling_factor=cfg.routed_scaling_factor, rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
        scale_q=cfg.scale_q, scale_kv=cfg.scale_kv,
    )
    return {**params, "hyper": {**hyper, **over}}


def run_paged(cfg, params, prompt, steps=STEPS, batch=3, slot=1, neighbours=()):
    """Prefill ``prompt``, then ``steps`` greedy decode steps in ``slot`` of a
    batch of ``batch``; ``neighbours`` are (slot, prompt) pairs that decode
    beside it. Returns (logits of every position fed (steps + 1, V), tokens
    fed, the pool)."""
    prefill, decode, _ = paged.make_paged_fns(M.paged_layer, cfg, block_size=BLOCK)
    pool = M.init_paged_pool(cfg, BLOCKS, BLOCK)
    alloc = BlockAllocator(BLOCKS, BLOCK)
    state = {}
    for i, p in [(slot, prompt), *neighbours]:
        table = BlockTable(alloc)
        table.reserve(len(p))
        table.length = len(p)
        toks = np.zeros((1, 32), np.int32)
        toks[0, : len(p)] = p
        logits, pool = prefill(params, jnp.asarray(toks), jnp.asarray([table.as_list(MAX_BLOCKS)], jnp.int32), pool,
                               jnp.int32(len(p)))
        state[i] = [table, np.asarray(logits[0])]
    got, fed = [state[slot][1]], list(prompt)
    for _ in range(steps):
        tk, ps = np.zeros((batch,), np.int32), np.zeros((batch,), np.int32)
        bt, ac = np.zeros((batch, MAX_BLOCKS), np.int32), np.zeros((batch,), bool)
        for i, (table, last) in state.items():
            tk[i], ps[i], ac[i] = int(last.argmax()), table.length, True
            table.append_token()
            bt[i] = table.as_list(MAX_BLOCKS)
        fed.append(int(tk[slot]))
        logits, pool = decode(params, jnp.asarray(tk), jnp.asarray(ps), jnp.asarray(bt), pool, jnp.asarray(ac))
        for i in state:
            state[i][1] = np.asarray(logits[i])
        got.append(state[slot][1])
    return np.stack(got), fed, pool


def reference_logits(ref_params, fed, n_prompt, steps=STEPS):
    seq = np.zeros((64,), np.int32)
    seq[: len(fed)] = fed
    return np.asarray(R.logits_at(ref_params, seq, np.arange(n_prompt - 1, n_prompt + steps), "f32"))


def rel_err(got, want):
    return float((np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)).max())


PROMPT = np.random.default_rng(0).integers(1, 255, 13).tolist()


@pytest.fixture(scope="module")
def served():
    """The uncut twin served through the paged programs, once."""
    cfg = twin()
    params = weights(cfg)
    got, fed, pool = run_paged(cfg, params, PROMPT)
    return cfg, params, got, fed, pool


# -- (a) the paged programs against the reference's full forward pass ----------


def test_prefill_then_decode_steps_give_the_references_logits_at_every_position(served):
    cfg, params, got, fed, pool = served
    want = reference_logits(for_reference(params, cfg), fed, len(PROMPT))
    assert got.shape == want.shape == (STEPS + 1, cfg.vocab_size)
    assert rel_err(got, want) < 1e-4
    counts = dict(zip(moe.COUNTS, np.asarray(pool["moe_counts"]).tolist()))
    # decode steps alone are counted: one live row, top-3, two layers, nothing absent
    assert counts["held"] + counts["zero"] == STEPS * 2 * 3 and counts["absent"] == 0
    assert 0 < counts["touched"] <= counts["held"]


def test_a_share_of_the_experts_gives_the_references_logits_for_the_same_share():
    cfg = twin(experts_held=4, expert_offset=2)
    params = weights(cfg, seed=1)
    got, fed, pool = run_paged(cfg, params, PROMPT)
    assert rel_err(got, reference_logits(for_reference(params, cfg), fed, len(PROMPT))) < 1e-4
    assert np.asarray(pool["moe_counts"])[2] > 0  # some choices went to experts held elsewhere
    whole = reference_logits(for_reference(params, cfg, expert_offset=0), fed, len(PROMPT))
    assert rel_err(got, whole) > 1e-3  # and the offset is not decoration


# -- (b) the absorbed decode path against the unabsorbed one ---------------------


def test_absorbed_attention_over_latent_rows_equals_per_head_attention():
    rng = np.random.default_rng(1)
    s, h, r, dn, dr, dv = 11, 4, 16, 8, 4, 8
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    q_n, q_r, ckv, k_r, wkvb = f(s, h, dn), f(s, h, dr), f(s, r), f(s, dr), f(r, h, dn + dv)
    pos = jnp.arange(s)
    q_r, k_r = rope_interleaved(q_r, pos, 1e4), rope_interleaved(k_r, pos, 1e4)
    kv = jnp.einsum("sr,rhk->shk", ckv, wkvb)
    scale = (dn + dr) ** -0.5
    unabsorbed = latent_prefill_attention(q_n, q_r, kv[..., :dn], k_r, kv[..., dn:], scale=scale, block_q=4)
    # every position as a decode step over the rows up to it, padded to 16 rows
    rows = jnp.zeros((s, 16, r + dr)).at[:, :s].set(jnp.concatenate([ckv, k_r], -1)[None])
    q_l = jnp.einsum("bhn,rhn->bhr", q_n, wkvb[..., :dn])
    o_l = latent_decode_attention(q_l, q_r, rows, pos + 1, scale=scale)
    absorbed = jnp.einsum("bhr,rhv->bhv", o_l, wkvb[..., dn:])
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(unabsorbed), atol=2e-5)


# -- (c) the shares add up ---------------------------------------------------------


def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    cfg = twin()
    params = weights(cfg, seed=2)
    u = jax.random.normal(jax.random.PRNGKey(3), (24, cfg.hidden_size))
    layer = {k: params[k][0] for k in ("router", "router_bias", "e_gate", "e_up", "e_down")}
    kw = dict(n_routed=cfg.n_routed_experts, top_k=cfg.moe_topk, scale=cfg.routed_scaling_factor)
    # the uncut reference: every expert held
    ref = for_reference(params, cfg)
    hy = R.hyper(ref)
    want = np.asarray(R.moe(u, ref, 0, hy, "f32"))
    weights_, chosen = R.route(u, ref["router"][0], ref["router_bias"][0], hy, "f32")
    identity = np.asarray(R.identity_part(u, weights_, chosen, hy))
    total, rows = np.zeros_like(want), np.zeros(len(moe.COUNTS), np.int64)
    for offset in range(0, cfg.n_routed_experts, 2):
        share = {**layer, **{k: layer[k][offset:offset + 2] for k in ("e_gate", "e_up", "e_down")}}
        y, counts = moe.expert_layer(share, u, expert_offset=offset, **kw)
        total += np.asarray(y) - identity  # every chip adds the identity part for its own tokens: counted once
        rows += np.asarray(counts)
    np.testing.assert_allclose(total + identity, want, atol=2e-5)
    held, zero, absent = rows[:3]
    # a routed row is held by one share and absent from the three others; an identity row is every share's
    assert zero % 4 == 0 and held + zero // 4 == 24 * 3 and absent == 3 * held


# -- (d) what the comparison has to catch -------------------------------------------


def _wrong_block(kind):
    def block(x, params, li, hy, precision):
        eps = hy["rms_norm_eps"]
        a = x + R.mla(R.rms_norm(x, params["in_norm"][li, 0], eps), params, li, 0, hy, precision)
        u = R.rms_norm(a, params["post_norm"][li, 0], eps)
        b = a + R.ffn(u, params, li, 0, precision)
        c = b + R.mla(R.rms_norm(b, params["in_norm"][li, 1], eps), params, li, 1, hy, precision)
        n_c = R.rms_norm(c, params["post_norm"][li, 1], eps)
        if kind == "moe_fed_x":
            return c + R.ffn(n_c, params, li, 1, precision) + R.moe(x, params, li, hy, precision)
        if kind == "moe_fed_normed_c":
            return c + R.ffn(n_c, params, li, 1, precision) + R.moe(n_c, params, li, hy, precision)
        # kind == "moe_added_after_ffn0": the expert layer's output joins before the second half
        b = b + R.moe(u, params, li, hy, precision)
        c = b + R.mla(R.rms_norm(b, params["in_norm"][li, 1], eps), params, li, 1, hy, precision)
        return c + R.ffn(R.rms_norm(c, params["post_norm"][li, 1], eps), params, li, 1, precision)

    return block


def _rotate_half(x, positions, theta):
    d = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * (1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)))
    if x.ndim == 3:
        ang = ang[:, None, :]
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _route(kind):
    def route(u, router, bias, hy, precision):
        p = jax.nn.softmax(jnp.einsum("sd,dn->sn", u, router.astype(jnp.float32), precision=R.HIGHEST), axis=-1)
        _, chosen = jax.lax.top_k(p + bias, hy["moe_topk"])
        w = jnp.take_along_axis(p + bias if kind == "bias_in_the_weights" else p, chosen, axis=-1)
        if kind == "renormalised":
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        return hy["routed_scaling_factor"] * w, chosen

    return route


FAULTS = {
    "identity_experts_left_out": dict(patch=("identity_part", lambda u, w, c, hy: jnp.zeros_like(u))),
    "routed_experts_left_out": dict(patch=("routed_part", lambda u, w, c, params, li, hy, precision: jnp.zeros_like(u))),
    "moe_fed_x": dict(patch=("block", _wrong_block("moe_fed_x"))),
    "moe_fed_normed_c": dict(patch=("block", _wrong_block("moe_fed_normed_c"))),
    "moe_added_after_ffn0": dict(patch=("block", _wrong_block("moe_added_after_ffn0"))),
    "scale_q_left_out": dict(hyper={"scale_q": 1.0}),
    "scale_kv_left_out": dict(hyper={"scale_kv": 1.0}),
    "rotate_half": dict(patch=("rope", _rotate_half)),
    "weights_renormalised": dict(patch=("route", _route("renormalised"))),
    "bias_in_the_weights": dict(patch=("route", _route("bias_in_the_weights"))),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_reference_with_this_fault_is_told_from_the_program(served, fault, monkeypatch):
    cfg, params, got, fed, _ = served
    spec = FAULTS[fault]
    if "patch" in spec:
        monkeypatch.setattr(R, *spec["patch"])
    want = reference_logits(for_reference(params, cfg, **spec.get("hyper", {})), fed, len(PROMPT))
    assert rel_err(got, want) > 1e-3, fault


# -- (e) no row is dropped ------------------------------------------------------------


def test_no_row_is_dropped_when_every_token_chooses_one_expert():
    d, f, t = 16, 32, 40
    params = moe.init_expert_params(jax.random.PRNGKey(0), d, f, held=2, n_outputs=4)
    params["router_bias"] = jnp.asarray([10.0, -10.0, -10.0, -10.0])  # every token's one choice is expert 0
    u = jax.random.normal(jax.random.PRNGKey(1), (t, d))
    y, counts = jax.jit(lambda rows: moe.expert_layer(params, rows, n_routed=2, top_k=1, scale=6.0))(u)
    w, chosen = moe.route(u, params["router"], params["router_bias"], top_k=1, scale=6.0)
    assert np.all(np.asarray(chosen) == 0) and np.asarray(counts).tolist() == [t, 0, 0, 1, t, 1, 1]
    dense = (jax.nn.silu(u @ params["e_gate"][0]) * (u @ params["e_up"][0])) @ params["e_down"][0]
    np.testing.assert_allclose(np.asarray(y), np.asarray(w * dense), atol=1e-5)
    assert np.all(np.abs(np.asarray(y)).sum(-1) > 0)  # every one of the 40 rows came through


def test_rows_that_are_not_tokens_route_nowhere():
    params = moe.init_expert_params(jax.random.PRNGKey(0), 16, 32, held=4, n_outputs=6)
    u = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
    live = jnp.asarray([True, False] * 4)
    layer = jax.jit(lambda rows, live=None: moe.expert_layer(params, rows, n_routed=4, top_k=2, scale=6.0, live=live))
    y, counts = layer(u, live)
    assert np.all(np.asarray(y)[1::2] == 0) and int(np.asarray(counts)[:3].sum()) == 4 * 2
    alone, _ = layer(u[::2])
    np.testing.assert_allclose(np.asarray(y)[::2], np.asarray(alone), atol=1e-6)


# -- who shares the step --------------------------------------------------------------


def test_a_sequences_logits_do_not_depend_on_who_shares_its_step(served):
    cfg, params, got, fed, _ = served
    others = [(0, list(range(3, 20))), (2, list(range(40, 47)))]
    among, fed_among, _ = run_paged(cfg, params, PROMPT, neighbours=others)
    assert fed_among == fed
    np.testing.assert_array_equal(among, got)


# -- (f) the engine end to end ----------------------------------------------------------


def test_the_engine_serves_the_replayed_tokens_and_reports_the_latent_pool():
    server = LLMServer(TWIN, dict(block_size=BLOCK, num_blocks=BLOCKS, max_batch=4, max_blocks_per_seq=MAX_BLOCKS),
                       weight_seed=5)
    try:
        eng = server._engine
        cfg = eng.model_cfg
        assert isinstance(cfg, M.LongcatConfig) and paged_model(cfg) is M
        rng = np.random.default_rng(7)
        prompts = [rng.integers(1, 255, n).tolist() for n in (5, 9, 14, 17, 21, 30)]
        streams = [server.generate(p, max_new_tokens=6 + i) for i, p in enumerate(prompts)]  # 6 requests, 4 slots
        served_tokens = [list(s) for s in streams]
        for i, (p, toks) in enumerate(zip(prompts, served_tokens)):
            _, fed, _ = run_paged(cfg, eng.params, p, steps=5 + i, batch=4, slot=0)
            assert toks[:-1] == fed[len(p):], i
        stats = server.kv_stats()
        row = -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128  # stored rows are whole lanes
        assert stats["bytes_per_block"] == 2 * cfg.num_layers * BLOCK * row * 4 == M.paged_block_bytes(cfg, BLOCK)
        pool = eng._pool["latent"]
        assert stats["bytes_per_block"] * BLOCKS == pool.size * pool.dtype.itemsize
        assert stats["blocks_total"] == BLOCKS - 1 and stats["blocks_free"] == BLOCKS - 1
        eng._moe_copy = (M.routing_counts(eng._pool), eng.decode_steps)
        eng._fold_routing_counts()
        held, zero, absent, touched, peak, windows, pairs = eng._moe_total
        assert held + zero == sum(5 + i for i in range(6)) * cfg.num_layers * cfg.moe_topk and absent == 0
        # every expert is held, so a call's window is all its rows: one a layer a step that routed a row to an expert,
        # and it is one row tile: every touched expert is visited once
        assert 0 < windows <= eng.decode_steps * cfg.num_layers and pairs == touched
    finally:
        server._engine.shutdown()


def test_a_dict_names_its_model_kind_and_one_that_names_none_is_a_transformer():
    from ray_tpu.models import generation
    from ray_tpu.models.transformer import TransformerConfig
    from ray_tpu.serve.llm.deployment import TINY_MODEL

    assert isinstance(_resolve_model_cfg(TINY_MODEL), TransformerConfig)
    assert paged_model(_resolve_model_cfg(None)) is generation
    cfg = twin(experts_held=2, expert_offset=6)
    assert (cfg.experts_held, cfg.n_routed_experts, cfg.cache_row) == (2, 8, 20)
    assert dataclasses.replace(cfg, experts_held=None, expert_offset=0).experts_held == 8
    with pytest.raises(ValueError):
        _resolve_model_cfg({**TWIN, "kind": "no_such_model"})
    with pytest.raises(ValueError):
        twin(experts_held=4, expert_offset=6)


# -- what 32 slots of streams found in the runtime -------------------------------------


def test_a_put_wakes_the_waiters_it_completes_and_no_others():
    """40 consumers of 40 token streams each wait on their own next item in
    the driver's memory store. A commit must wake the one it completes: with
    one condition for all, every token of any stream woke all 40, and a
    replica's streams together stopped at ~600 items/s (PERF.md, PR 29)."""
    import threading

    from ray_tpu._private.ids import ObjectID
    from ray_tpu._private.scheduler import MemoryStore

    store = MemoryStore()
    oids = [ObjectID.from_random() for _ in range(8)]
    woken, results = [], {}

    def consumer(i):
        results[i] = store.wait_num([oids[i]], 1, 30.0)

    threads = [threading.Thread(target=consumer, args=(i,)) for i in range(len(oids))]
    for th in threads:
        th.start()
    deadline = time.monotonic() + 10
    while len(store._waiters) < len(oids) and time.monotonic() < deadline:
        time.sleep(0.01)
    waiters = {i: store._waiters[o][0] for i, o in enumerate(oids)}
    for i, w in waiters.items():  # note every wake-up of every waiter's condition
        notify = w["cv"].notify
        w["cv"].notify = lambda n=1, i=i, notify=notify: (woken.append(i), notify(n))[1]
    store.put(oids[3], ("inline", b"x"))
    threads[3].join(timeout=10)
    assert woken == [3] and results[3] == [oids[3]] and all(th.is_alive() for j, th in enumerate(threads) if j != 3)
    store.put_many([(o, ("inline", b"x")) for j, o in enumerate(oids) if j != 3])
    for th in threads:
        th.join(timeout=10)
    assert sorted(woken) == list(range(len(oids))) and not store._waiters
    assert store.wait_for(oids, 0.0) == set(oids) and store.wait_num(oids[:2], 2, 0.0) == oids[:2]
    missing = ObjectID.from_random()
    assert store.wait_for([missing], 0.05) == set() and not store._waiters  # a timeout leaves no waiter behind
