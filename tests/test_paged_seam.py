"""The seams of ``ray_tpu/models``: one block under its three ``attend``s
(training's, the dense cache's, the paged pool's) gives the same logits, a
model kind costs ``models/paged.py`` four things and one table entry (a
made-up kind, defined here, served by the engine), the fifth thing a kind
may give, how its stacked weights lie on the device, changes no token, and the
flat K/V pool's one owner (``models/flat_kv.py``) writes and reads a call's
rows as dense attention would, apart from any kind. CPU, float32."""

import dataclasses
import logging
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from ray_tpu import models  # noqa: E402
from ray_tpu.models import flat_kv, generation as G, paged, transformer as T  # noqa: E402
from ray_tpu.ops.attention import attention  # noqa: E402
from ray_tpu.ops.layers import gelu, rms_norm  # noqa: E402
from ray_tpu.serve.llm.deployment import LLMServer  # noqa: E402
from ray_tpu.serve.llm.engine import EngineConfig, InferenceEngine  # noqa: E402

BLOCK, BLOCKS, MAX_BLOCKS, BUCKET = 4, 32, 8, 16

# -- (a) the two block forms the zoo has, in the three views ---------------------------

SHAPE = dict(vocab_size=96, d_model=32, n_layers=2, n_heads=4, d_ff=64, max_seq_len=32, dtype=jnp.float32, remat=False)
FORMS = {
    "gptj": T.TransformerConfig(**SHAPE, parallel_block=True, use_swiglu=False, tie_embeddings=False),
    "llama": T.TransformerConfig(**SHAPE, parallel_block=False, use_swiglu=True, n_kv_heads=2),
}
TOKENS = np.random.default_rng(11).integers(1, 95, 14).astype(np.int32)


def dense_logits(cfg, params, prompt_len):
    """The prompt prefilled, the rest decoded a token a step, through the
    dense cache: the logits of positions ``prompt_len - 1 ..``."""
    prefill, decode = G.make_decode_fns(cfg, len(TOKENS))
    logits, cache = prefill(params, jnp.asarray(TOKENS[None, :prompt_len]), G.init_kv_cache(cfg, 1, len(TOKENS)))
    out = [logits[0]]
    for t in TOKENS[prompt_len:]:
        logits, cache = decode(params, jnp.asarray([[t]]), cache)
        out.append(logits[0])
    return np.stack(out)


def paged_logits(cfg, params, prompt_len):
    """The same through the paged programs, in slot 1 of 3 with blocks out of
    order; the padded prefill and the two empty slots must not show."""
    prefill, decode, greedy = G.make_paged_fns(cfg, block_size=BLOCK)
    pool = G.init_paged_pool(cfg, BLOCKS, BLOCK)
    table = np.zeros((3, MAX_BLOCKS), np.int32)
    table[1, :4] = [7, 3, 21, 12]
    toks = np.zeros((1, BUCKET), np.int32)
    toks[0, :prompt_len] = TOKENS[:prompt_len]
    logits, pool = prefill(params, jnp.asarray(toks), jnp.asarray(table[1:2]), pool, jnp.int32(prompt_len))
    out = [logits[0]]
    active = jnp.asarray([False, True, False])
    for i, t in enumerate(TOKENS[prompt_len:]):
        args = (jnp.asarray([0, t, 0], jnp.int32), jnp.asarray([0, prompt_len + i, 0], jnp.int32), jnp.asarray(table))
        logits, pool = decode(params, *args, pool, active)
        token, pool = greedy(params, *args, pool, active)  # writes the same rows again
        assert int(token[1]) == int(jnp.argmax(logits[1]))
        out.append(logits[1])
    return np.stack(out)


@pytest.mark.parametrize("view", [dense_logits, paged_logits])
@pytest.mark.parametrize("form", list(FORMS))
def test_one_block_gives_the_same_logits_in_training_and_over_both_caches(form, view):
    cfg = FORMS[form]
    params = T.init_params(jax.random.PRNGKey(3), cfg)
    want = np.asarray(T.forward(params, jnp.asarray(TOKENS[None]), cfg)[0], np.float32)
    for prompt_len in (1, 6):  # from the first position: every position's logits; then a prefill of several
        got = view(cfg, params, prompt_len)
        assert got.shape == want[prompt_len - 1:].shape
        np.testing.assert_allclose(got, want[prompt_len - 1:], atol=2e-5, rtol=2e-4)


# -- (b) a made-up third kind: what a ``model_config`` PR costs the program ------------
#
# Its mixer is no attention: position t reads the mean of the rows that
# positions 0..t wrote. Its pool is its own shape, with a counter leaf of its own.


@dataclasses.dataclass(frozen=True)
class ToyConfig:
    vocab_size: int = 64
    width: int = 16
    n_layers: int = 2
    max_seq_len: int = 64
    dtype: type = jnp.float32


def init_params(key, cfg):
    ke, km, ku, kd, kh = jax.random.split(key, 5)
    shape = (cfg.n_layers, cfg.width, cfg.width)
    return {
        "embed": jax.random.normal(ke, (cfg.vocab_size, cfg.width), cfg.dtype),
        "w_mix": jax.random.normal(km, shape, cfg.dtype) * cfg.width ** -0.5,
        "w_up": jax.random.normal(ku, shape, cfg.dtype) * cfg.width ** -0.5,
        "w_down": jax.random.normal(kd, shape, cfg.dtype) * cfg.width ** -0.5,
        "final_norm": jnp.ones((cfg.width,), jnp.float32),
        "unembed": jax.random.normal(kh, (cfg.width, cfg.vocab_size), cfg.dtype) * cfg.width ** -0.5,
    }


def init_paged_pool(cfg, num_blocks, block_size):
    return {"rows": jnp.zeros((cfg.n_layers, num_blocks, block_size, cfg.width), cfg.dtype),
            "rows_written": jnp.zeros((), jnp.int32)}


def paged_block_bytes(cfg, block_size):
    return cfg.n_layers * block_size * cfg.width * jnp.dtype(cfg.dtype).itemsize


def paged_layer(cfg, params, step):
    b, s = step.positions.shape
    bs = step.block_size
    upto = (jnp.arange(step.block_tables.shape[1] * bs) <= step.positions[..., None]).astype(cfg.dtype)  # (B, S, M)

    def layer(x, pool, li):
        row = x @ params["w_mix"][li]
        rows = pool["rows"].at[li, step.write_slots // bs, step.write_slots % bs].set(row.reshape(b * s, -1))
        seen = rows[li, step.block_tables].reshape(b, -1, cfg.width)  # row index == position
        x = x + jnp.einsum("bsm,bmd->bsd", upto, seen) / (step.positions[..., None] + 1)
        x = x + gelu(x @ params["w_up"][li]) @ params["w_down"][li]
        return x, {"rows": rows, "rows_written": pool["rows_written"] + jnp.sum(step.live)}

    return layer


def toy_next_token(cfg, params, tokens):
    """The plain model over the whole sequence, no cache: the next token."""
    x = params["embed"][jnp.asarray(tokens)]
    for li in range(cfg.n_layers):
        mixed = jnp.cumsum(x @ params["w_mix"][li], axis=0) / jnp.arange(1, len(tokens) + 1)[:, None]
        x = x + mixed
        x = x + gelu(x @ params["w_up"][li]) @ params["w_down"][li]
    return int(jnp.argmax(rms_norm(x[-1], params["final_norm"]) @ params["unembed"]))


def test_a_made_up_kind_is_served_through_its_table_entry_alone():
    models.PAGED_KINDS["toy"] = (__name__, "ToyConfig")
    try:
        server = LLMServer({"kind": "toy", "vocab_size": 48},
                           dict(block_size=BLOCK, num_blocks=BLOCKS, max_batch=2, max_blocks_per_seq=MAX_BLOCKS),
                           weight_seed=9)
        try:
            eng = server._engine
            cfg = eng.model_cfg
            assert type(cfg) is ToyConfig and cfg.vocab_size == 48
            prompts = [[5, 9, 2], [7] * 9, [1, 2, 3, 4, 5, 6]]  # three requests on two slots
            served = [list(s) for s in [server.generate(p, max_new_tokens=7) for p in prompts]]
            for prompt, got in zip(prompts, served):
                seq = list(prompt)
                for token in got:
                    assert token == toy_next_token(cfg, eng.params, seq)
                    seq.append(token)
            # it names no layout: its parameters are the arrays it was given
            assert eng.placed == {} and server.loop_stats()["placed"] == {}
            stats = server.kv_stats()
            assert stats["bytes_per_block"] == cfg.n_layers * BLOCK * cfg.width * 4
            assert stats["blocks_free"] == stats["blocks_total"] == BLOCKS - 1
            # a prompt's rows and six tokens fed back, a layer each: the pool's own leaf rode along
            assert int(eng._pool["rows_written"]) == cfg.n_layers * sum(len(p) + 6 for p in prompts)
        finally:
            server._engine.shutdown()
    finally:
        del models.PAGED_KINDS["toy"]
    with pytest.raises(ValueError, match="unknown model kind 'toy'"):
        LLMServer({"kind": "toy"})


# -- (b') sections: a model of unlike layers in one paged program ------------------------
#
# The made-up kind again, with a leading layer of another shape: layer 0 has no MLP and
# mixes twice as hard; its tensors are its own ("lead_mix", unstacked), the alike layers'
# are stacked over those layers alone and read from the section's first layer on.


@dataclasses.dataclass(frozen=True)
class LeadToyConfig(ToyConfig):
    n_layers: int = 3  # the leading layer, then two alike


def lead_params(key, cfg):
    params = init_params(key, dataclasses.replace(cfg, n_layers=cfg.n_layers - 1))
    return {**params, "lead_mix": jax.random.normal(jax.random.fold_in(key, 7), (cfg.width, cfg.width), cfg.dtype)
            * cfg.width ** -0.5}


def lead_paged_layer(cfg, params, step):
    b, s = step.positions.shape
    bs = step.block_size
    upto = (jnp.arange(step.block_tables.shape[1] * bs) <= step.positions[..., None]).astype(cfg.dtype)

    def mix(x, pool, li, w):  # the layer's rows go to the pool's layer li, whichever section runs it
        rows = pool["rows"].at[li, step.write_slots // bs, step.write_slots % bs].set((x @ w).reshape(b * s, -1))
        seen = rows[li, step.block_tables].reshape(b, -1, cfg.width)
        mixed = jnp.einsum("bsm,bmd->bsd", upto, seen) / (step.positions[..., None] + 1)
        return mixed, {"rows": rows, "rows_written": pool["rows_written"] + jnp.sum(step.live)}

    def lead(x, pool, li):
        mixed, pool = mix(x, pool, li, params["lead_mix"])
        return x + 2 * mixed, pool

    def alike(x, pool, li):
        own = li - 1  # this section's tensors are stacked from its own first layer
        mixed, pool = mix(x, pool, li, params["w_mix"][own])
        x = x + mixed
        return x + gelu(x @ params["w_up"][own]) @ params["w_down"][own], pool

    return [(lead, 1), (alike, cfg.n_layers - 1)]


def lead_next_token(cfg, params, tokens):
    x = params["embed"][jnp.asarray(tokens)]
    count = jnp.arange(1, len(tokens) + 1)[:, None]
    x = x + 2 * jnp.cumsum(x @ params["lead_mix"], axis=0) / count
    for li in range(cfg.n_layers - 1):
        x = x + jnp.cumsum(x @ params["w_mix"][li], axis=0) / count
        x = x + gelu(x @ params["w_up"][li]) @ params["w_down"][li]
    return int(jnp.argmax(rms_norm(x[-1], params["final_norm"]) @ params["unembed"]))


def test_a_kind_of_two_sections_is_served_with_the_pool_carried_through_both():
    lead = type(sys)("lead_toy")  # the kind's module: the toy's, with the two things that differ
    lead.__dict__.update(LeadToyConfig=LeadToyConfig, init_params=lead_params, paged_layer=lead_paged_layer,
                         init_paged_pool=init_paged_pool, paged_block_bytes=paged_block_bytes)
    sys.modules["lead_toy"] = lead
    models.PAGED_KINDS["lead_toy"] = ("lead_toy", "LeadToyConfig")
    try:
        server = LLMServer({"kind": "lead_toy", "vocab_size": 48},
                           dict(block_size=BLOCK, num_blocks=BLOCKS, max_batch=2, max_blocks_per_seq=MAX_BLOCKS),
                           weight_seed=4)
        try:
            eng = server._engine
            cfg = eng.model_cfg
            assert eng._pool["rows"].shape[0] == cfg.n_layers == 3
            prompts = [[5, 9, 2], [7] * 9, [1, 2, 3, 4, 5, 6]]
            for prompt in prompts:
                seq = list(prompt)
                for token in server.generate(prompt, max_new_tokens=7):
                    assert token == lead_next_token(cfg, eng.params, seq)
                    seq.append(token)
            # every layer of both sections wrote its rows: the pool's own leaf rode through both scans
            assert int(eng._pool["rows_written"]) == cfg.n_layers * sum(len(p) + 6 for p in prompts)
        finally:
            server._engine.shutdown()
    finally:
        del models.PAGED_KINDS["lead_toy"], sys.modules["lead_toy"]
    # sections that do not cover the model's layers are refused when the program is traced
    short = lambda cfg, params, step: lead_paged_layer(cfg, params, step)[:1]  # noqa: E731
    cfg = LeadToyConfig()
    prefill, _, _ = paged.make_paged_fns(short, cfg, block_size=BLOCK)
    with pytest.raises(ValueError, match=r"cover \[1\] layers of 3"):
        prefill(lead_params(jax.random.PRNGKey(0), cfg), jnp.zeros((1, BUCKET), jnp.int32),
                jnp.zeros((1, MAX_BLOCKS), jnp.int32), init_paged_pool(cfg, BLOCKS, BLOCK), jnp.int32(3))


# -- (b'') a section whose body is several layers a call, and a state row a sequence ------
#
# The made-up kind with four layers in a period of two: an even layer is the toy's, an odd one
# has no MLP and adds, beside its mix, the running mean of its inputs over the sequence so far:
# a state that is not rows a position (one row a sequence and an odd layer, a count with it),
# found through the table's last column.


@dataclasses.dataclass(frozen=True)
class PairToyConfig(ToyConfig):
    n_layers: int = 4


def pair_init_pool(cfg, num_blocks, block_size, state_rows):
    return {**init_paged_pool(cfg, num_blocks, block_size),
            "sums": jnp.zeros((cfg.n_layers // 2, state_rows, cfg.width), cfg.dtype),
            "seen": jnp.zeros((cfg.n_layers // 2, state_rows), jnp.int32)}


def pair_state_bytes(cfg):
    return (cfg.n_layers // 2) * (cfg.width * jnp.dtype(cfg.dtype).itemsize + 4)


def pair_paged_layer(cfg, params, step):
    b, s = step.positions.shape
    bs = step.block_size
    upto = (jnp.arange(step.block_tables.shape[1] * bs) <= step.positions[..., None]).astype(cfg.dtype)
    live = step.live.reshape(b, s)

    def mix(x, pool, li):
        rows = pool["rows"].at[li, step.write_slots // bs, step.write_slots % bs].set((x @ params["w_mix"][li]).reshape(b * s, -1))
        seen = rows[li, step.block_tables].reshape(b, -1, cfg.width)
        mixed = jnp.einsum("bsm,bmd->bsd", upto, seen) / (step.positions[..., None] + 1)
        return mixed, {**pool, "rows": rows, "rows_written": pool["rows_written"] + jnp.sum(step.live)}

    def pair(x, pool, li):  # layers li and li + 1; li the pair's first
        mixed, pool = mix(x, pool, li)
        x = x + mixed
        x = x + gelu(x @ params["w_up"][li]) @ params["w_down"][li]
        mixed, pool = mix(x, pool, li + 1)
        own, row = li // 2, step.state_rows
        # a prefill starts the row's sum over; a decode step adds to what the row holds
        before = jnp.where(s > 1, 0.0, pool["sums"][own, row])
        count = jnp.where(s > 1, 0, pool["seen"][own, row])
        running = before[:, None] + jnp.cumsum(jnp.where(live[..., None], x, 0.0), axis=1)
        mean = running / (count[:, None, None] + jnp.cumsum(live, axis=1)[..., None]).clip(1)
        keep = live.any(axis=1)  # an inactive slot names the null row: it stays zeros
        pool = {**pool,
                "sums": pool["sums"].at[own, row].set(jnp.where(keep[:, None], running[:, -1], pool["sums"][own, row])),
                "seen": pool["seen"].at[own, row].set(jnp.where(keep, count + jnp.sum(live, axis=1), pool["seen"][own, row]))}
        return x + mixed + mean, pool

    return [(pair, cfg.n_layers, 2)]


def pair_next_token(cfg, params, tokens):
    x = params["embed"][jnp.asarray(tokens)]
    count = jnp.arange(1, len(tokens) + 1)[:, None]
    for li in range(0, cfg.n_layers, 2):
        x = x + jnp.cumsum(x @ params["w_mix"][li], axis=0) / count
        x = x + gelu(x @ params["w_up"][li]) @ params["w_down"][li]
        x = x + jnp.cumsum(x @ params["w_mix"][li + 1], axis=0) / count + jnp.cumsum(x, axis=0) / count
    return int(jnp.argmax(rms_norm(x[-1], params["final_norm"]) @ params["unembed"]))


def test_a_kind_whose_section_covers_two_layers_a_call_is_served_with_a_state_row_a_sequence():
    pair = type(sys)("pair_toy")
    pair.__dict__.update(PairToyConfig=PairToyConfig, init_params=init_params, paged_layer=pair_paged_layer,
                         init_paged_pool=pair_init_pool, paged_block_bytes=paged_block_bytes,
                         paged_state_bytes=pair_state_bytes)
    sys.modules["pair_toy"] = pair
    models.PAGED_KINDS["pair_toy"] = ("pair_toy", "PairToyConfig")
    try:
        server = LLMServer({"kind": "pair_toy", "vocab_size": 48},
                           dict(block_size=BLOCK, num_blocks=BLOCKS, max_batch=2, max_blocks_per_seq=MAX_BLOCKS + 1),
                           weight_seed=6)
        try:
            eng = server._engine
            cfg = eng.model_cfg
            # a row a slot and the null row; the table's last column is the row's, so a context of MAX_BLOCKS blocks
            assert eng._pool["sums"].shape == (2, 3, cfg.width) and eng.max_context == MAX_BLOCKS * BLOCK
            assert server.kv_stats()["state_rows_total"] == 2 and server.kv_stats()["state_bytes"] == 2 * (cfg.width * 4 + 4)
            prompts = [[5, 9, 2], [7] * 9, [1, 2, 3, 4, 5, 6], [3, 1]]  # four requests on two slots: rows change hands
            streams = [server.generate(p, max_new_tokens=7) for p in prompts]
            for prompt, got in zip(prompts, [list(s) for s in streams]):
                seq = list(prompt)
                for token in got:
                    assert token == pair_next_token(cfg, eng.params, seq)
                    seq.append(token)
            assert int(eng._pool["rows_written"]) == cfg.n_layers * sum(len(p) + 6 for p in prompts)
            assert not np.asarray(eng._pool["sums"][:, 0]).any() and not np.asarray(eng._pool["seen"][:, 0]).any()
            assert server.kv_stats()["state_rows_used"] == 0
        finally:
            server._engine.shutdown()
    finally:
        del models.PAGED_KINDS["pair_toy"], sys.modules["pair_toy"]
    # a section whose layers are not whole calls is refused when the program is traced
    cfg = PairToyConfig()
    odd = lambda cfg, params, step: [(pair_paged_layer(cfg, params, step)[0][0], 4, 3)]  # noqa: E731
    prefill, _, _ = paged.make_paged_fns(odd, cfg, block_size=BLOCK, state_rows=True)
    with pytest.raises(ValueError, match=r"cover \[4\] layers of 4, \[3\] a call"):
        prefill(init_params(jax.random.PRNGKey(0), cfg), jnp.zeros((1, BUCKET), jnp.int32),
                jnp.zeros((1, MAX_BLOCKS + 1), jnp.int32), pair_init_pool(cfg, BLOCKS, BLOCK, 3), jnp.int32(3))


# -- (b''') a value carried beside the residual, and a section that a prefill runs on one position ------
#
# The made-up kind with three layers: two of the toy's, the second of which hands its output on as a
# memo; then one that writes no rows: it adds tanh(memo) times the running mean of the rows layer 1
# wrote, and an MLP. Everything it reads a decode step could give it, so a prefill runs it on the
# prompt's last position alone (``paged.Carried``; the section's fourth entry).


@dataclasses.dataclass(frozen=True)
class MemoToyConfig(ToyConfig):
    n_layers: int = 3


def memo_init_pool(cfg, num_blocks, block_size):
    return {**init_paged_pool(cfg, num_blocks, block_size), "tail_positions": jnp.zeros((), jnp.int32)}


def memo_paged_layer(cfg, params, step):
    b, s = step.positions.shape
    bs = step.block_size
    upto = (jnp.arange(step.block_tables.shape[1] * bs) <= step.positions[..., None]).astype(cfg.dtype)
    toy = paged_layer(cfg, params, step)

    def first(x, pool, memo, li):
        x, rest = toy(x, pool, li)
        return x, {**pool, **rest}, jnp.where(li == 1, x, memo)

    def last(x, pool, memo, li):
        seen = pool["rows"][1, step.block_tables].reshape(b, -1, cfg.width)  # layer 1's rows: nothing written here
        x = x + jnp.tanh(memo) * jnp.einsum("bsm,bmd->bsd", upto, seen) / (step.positions[..., None] + 1)
        x = x + gelu(x @ params["w_up"][li]) @ params["w_down"][li]
        return x, {**pool, "tail_positions": pool["tail_positions"] + jnp.sum(step.live)}, memo

    return paged.Carried([(first, 2), (last, 1, 1, True)], jnp.zeros((b, s, cfg.width), cfg.dtype))


def memo_next_token(cfg, params, tokens):
    x = params["embed"][jnp.asarray(tokens)]
    count = jnp.arange(1, len(tokens) + 1)[:, None]
    for li in range(2):
        rows = x @ params["w_mix"][li]
        x = x + jnp.cumsum(rows, axis=0) / count
        x = x + gelu(x @ params["w_up"][li]) @ params["w_down"][li]
    x = x + jnp.tanh(x) * jnp.cumsum(rows, axis=0) / count
    x = x + gelu(x @ params["w_up"][2]) @ params["w_down"][2]
    return int(jnp.argmax(rms_norm(x[-1], params["final_norm"]) @ params["unembed"]))


def test_a_kind_with_a_carried_leaf_and_a_last_position_section_is_served():
    memo = type(sys)("memo_toy")
    memo.__dict__.update(MemoToyConfig=MemoToyConfig, init_params=init_params, paged_layer=memo_paged_layer,
                         init_paged_pool=memo_init_pool, paged_block_bytes=paged_block_bytes)
    sys.modules["memo_toy"] = memo
    models.PAGED_KINDS["memo_toy"] = ("memo_toy", "MemoToyConfig")
    try:
        server = LLMServer({"kind": "memo_toy", "vocab_size": 48},
                           dict(block_size=BLOCK, num_blocks=BLOCKS, max_batch=2, max_blocks_per_seq=MAX_BLOCKS),
                           weight_seed=2)
        try:
            eng = server._engine
            cfg = eng.model_cfg
            prompts = [[5, 9, 2], [7] * 9, [1, 2, 3, 4, 5, 6]]  # three requests on two slots
            streams = [server.generate(p, max_new_tokens=7) for p in prompts]
            for prompt, got in zip(prompts, [list(s) for s in streams]):
                seq = list(prompt)
                for token in got:
                    assert token == memo_next_token(cfg, eng.params, seq)
                    seq.append(token)
            # the first section wrote every position's rows, two layers; the last ran one position a
            # prefill and one a sequence a decode step
            assert int(eng._pool["rows_written"]) == 2 * sum(len(p) + 6 for p in prompts)
            assert int(eng._pool["tail_positions"]) == sum(1 + 6 for p in prompts)
            assert "ring_rows" not in server.loop_stats()["fields"] and server.kv_stats()["ring_bytes"] == 0
        finally:
            server._engine.shutdown()
    finally:
        del models.PAGED_KINDS["memo_toy"], sys.modules["memo_toy"]
    # over every position (the fourth entry dropped) the same first token; after a last-position section none
    cfg = MemoToyConfig()
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6] + [0] * (BUCKET - 8)], jnp.int32)
    table = jnp.asarray([[1, 2] + [0] * (MAX_BLOCKS - 2)], jnp.int32)
    args = (params, tokens, table)
    every = lambda cfg, params, step: paged.Carried(  # noqa: E731
        [sec[:3] for sec in memo_paged_layer(cfg, params, step).sections], jnp.zeros((1, BUCKET, cfg.width), cfg.dtype))
    short, pool_short = paged.make_paged_fns(memo_paged_layer, cfg, block_size=BLOCK)[0](
        *args, memo_init_pool(cfg, BLOCKS, BLOCK), jnp.int32(8))
    whole, pool_whole = paged.make_paged_fns(every, cfg, block_size=BLOCK)[0](*args, memo_init_pool(cfg, BLOCKS, BLOCK), jnp.int32(8))
    np.testing.assert_allclose(np.asarray(short), np.asarray(whole), atol=1e-5, rtol=1e-5)
    assert (int(pool_short["tail_positions"]), int(pool_whole["tail_positions"])) == (1, 8)
    np.testing.assert_array_equal(np.asarray(pool_short["rows"]), np.asarray(pool_whole["rows"]))
    wrong = lambda cfg, params, step: paged.Carried(  # noqa: E731
        [(memo_paged_layer(cfg, params, step).sections[1][0], 1, 1, True), (memo_paged_layer(cfg, params, step).sections[0][0], 2)],
        jnp.zeros((1, step.positions.shape[1], cfg.width), cfg.dtype))
    with pytest.raises(ValueError, match="followed by one over every position"):
        paged.make_paged_fns(wrong, cfg, block_size=BLOCK)[0](*args, memo_init_pool(cfg, BLOCKS, BLOCK), jnp.int32(8))


def _forward_paged_before_sections(paged_layer, cfg, params, tokens, positions, write_mask, block_tables, pool,
                                   block_size, last=None, state_rows=False):
    """``forward_paged`` as it stood before a kind could hand back sections (PR 32), kept here as the
    witness: one layer function, one scan over ``cfg.n_layers``."""
    b, s = tokens.shape
    pidx = jnp.clip(positions // block_size, 0, block_tables.shape[1] - 1)
    slot = jnp.take_along_axis(block_tables, pidx, axis=1) * block_size + positions % block_size
    null_slot = jnp.arange(b * s, dtype=slot.dtype) % block_size
    live = write_mask.reshape(-1)
    layer = paged_layer(cfg, params, paged.Step(
        positions, block_tables, block_size, jnp.where(live, slot.reshape(-1), null_slot), live,
        jnp.where(write_mask[:, 0], positions[:, 0] + 1, 0)))

    def body(carry, li):
        return layer(*carry, li), None

    (x, pool), _ = jax.lax.scan(body, (params["embed"][tokens], pool), jnp.arange(cfg.n_layers))
    return paged.head(cfg, params, x, last), pool


@pytest.mark.parametrize("kind", ["gptj", "longcat", "toy"])
def test_a_kind_of_one_function_traces_the_program_it_traced_before_sections(kind, monkeypatch):
    """The three programs of a kind that hands back one layer function, traced
    through today's ``forward_paged`` and through the one it replaced: the same
    jaxpr, letter for letter."""
    if kind == "gptj":
        cfg, layer = FORMS["gptj"], G.paged_layer
        params, pool = T.init_params(jax.random.PRNGKey(0), cfg), G.init_paged_pool(cfg, BLOCKS, BLOCK)
    elif kind == "longcat":
        from ray_tpu.models import longcat

        cfg = longcat.LongcatConfig(
            vocab_size=64, hidden_size=32, ffn_hidden_size=48, expert_ffn_hidden_size=16, num_layers=2,
            num_attention_heads=2, kv_lora_rank=8, q_lora_rank=16, qk_rope_head_dim=4, qk_nope_head_dim=4, v_head_dim=4,
            n_routed_experts=4, zero_expert_num=2, moe_topk=2, max_position_embeddings=64, dtype=jnp.float32)
        layer = longcat.paged_layer
        params, pool = longcat.init_params(jax.random.PRNGKey(0), cfg), longcat.init_paged_pool(cfg, BLOCKS, BLOCK)
    else:
        cfg, layer = ToyConfig(), paged_layer
        params, pool = init_params(jax.random.PRNGKey(0), cfg), init_paged_pool(cfg, BLOCKS, BLOCK)

    def traced():
        prefill, decode, greedy = paged.make_paged_fns(layer, cfg, block_size=BLOCK)
        step = (params, jnp.zeros((3,), jnp.int32), jnp.zeros((3,), jnp.int32), jnp.zeros((3, MAX_BLOCKS), jnp.int32),
                pool, jnp.ones((3,), bool))
        return [str(jax.make_jaxpr(prefill)(params, jnp.zeros((1, BUCKET), jnp.int32),
                                            jnp.zeros((1, MAX_BLOCKS), jnp.int32), pool, jnp.int32(5))),
                str(jax.make_jaxpr(decode)(*step)), str(jax.make_jaxpr(greedy)(*step))]

    now = traced()
    monkeypatch.setattr(paged, "forward_paged", _forward_paged_before_sections)
    assert traced() == now


# -- (c) how a kind's stacked weights lie on the device --------------------------------
#
# ``place_params`` asks the platform, which is the CPU here: a test that wants the
# placing steers ``jax.default_backend`` around that one call (the CPU backend takes a
# ``major_to_minor`` too) and lets the programs trace after it, on their CPU paths.

HEADS_MAJOR = {name: (0, 2, 1, 3) for name in ("wq", "wk", "wv")}


@pytest.mark.parametrize("case", ["the_cpu_backend", "a_kind_that_names_no_layout"])
def test_placing_leaves_the_parameters_alone_on(case, monkeypatch):
    if case == "the_cpu_backend":
        model, cfg = G, FORMS["gptj"]
    else:
        model, cfg = sys.modules[__name__], ToyConfig()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    params = model.init_params(jax.random.PRNGKey(1), cfg)
    placed, what = paged.place_params(model, cfg, params)
    assert placed is params and what == {}
    assert not any(x.is_deleted() for x in params.values())


@pytest.mark.parametrize("form", list(FORMS))
def test_placed_and_unplaced_parameters_give_the_same_greedy_tokens(form, monkeypatch):
    """24 greedy steps of two sequences through the paged programs: the
    parameters as ``init_params`` leaves them, and the same with ``wq``,
    ``wk``, ``wv`` heads-major in memory. Shape, key and value are the same;
    the originals are gone (the engine's memory has no room for both)."""
    cfg = FORMS[form]
    assert G.paged_layouts(cfg) == HEADS_MAJOR
    plain = T.init_params(jax.random.PRNGKey(5), cfg)
    given = T.init_params(jax.random.PRNGKey(5), cfg)
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        placed, what = paged.place_params(G, cfg, given)
    assert what == HEADS_MAJOR and placed.keys() == plain.keys()
    for name, x in placed.items():
        if name in HEADS_MAJOR:
            assert x.format.layout.major_to_minor == (0, 2, 1, 3) and given[name].is_deleted()
            assert x.shape == plain[name].shape and x.sharding == plain[name].sharding
            np.testing.assert_array_equal(x, plain[name])
        else:
            assert x is given[name]

    def greedy_tokens(params):
        prefill, _, greedy = G.make_paged_fns(cfg, block_size=BLOCK)
        pool = G.init_paged_pool(cfg, BLOCKS, BLOCK)
        table = np.zeros((2, MAX_BLOCKS), np.int32)
        table[0], table[1] = np.arange(1, 9), np.arange(9, 17)
        prompts = [TOKENS[:5], TOKENS[5:8]]
        tokens = []
        for i, prompt in enumerate(prompts):
            toks = np.zeros((1, BUCKET), np.int32)
            toks[0, :len(prompt)] = prompt
            logits, pool = prefill(params, jnp.asarray(toks), jnp.asarray(table[i:i + 1]), pool, jnp.int32(len(prompt)))
            tokens.append(int(jnp.argmax(logits[0])))
        out = [tokens]
        for t in range(24):
            positions = jnp.asarray([len(p) + t for p in prompts], jnp.int32)
            token, pool = greedy(params, jnp.asarray(out[-1], jnp.int32), positions, jnp.asarray(table), pool,
                                 jnp.asarray([True, True]))
            out.append([int(x) for x in token])
        return out

    assert greedy_tokens(placed) == greedy_tokens(plain)


def test_placing_keeps_the_sharding_a_tensor_has(monkeypatch):
    """A deployment over several chips: a tensor split over a mesh is re-laid
    shard by shard, on the sharding it came with."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    cfg = FORMS["gptj"]
    params = T.init_params(jax.random.PRNGKey(2), cfg)
    want = np.asarray(params["wq"])
    split = NamedSharding(Mesh(np.array(jax.devices()[:2]), ("tensor",)), PartitionSpec(None, None, "tensor", None))
    params["wq"] = jax.device_put(params["wq"], split)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    placed, _ = paged.place_params(G, cfg, params)
    assert placed["wq"].sharding == split and placed["wq"].format.layout.major_to_minor == (0, 2, 1, 3)
    np.testing.assert_array_equal(placed["wq"], want)


def test_the_engine_places_once_and_says_what_it_placed(monkeypatch, caplog):
    """The engine's start: the placed tree is ``eng.params``, ``loop_stats()``
    names it, one log line says it; the tokens it serves are the dense path's."""
    cfg = FORMS["llama"]
    given = T.init_params(jax.random.PRNGKey(7), cfg)
    ecfg = EngineConfig(block_size=BLOCK, num_blocks=BLOCKS, max_batch=2, max_blocks_per_seq=MAX_BLOCKS)
    caplog.set_level(logging.INFO, logger="ray_tpu.serve.llm.engine")
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        eng = InferenceEngine(given, cfg, ecfg, deployment="placed")
    try:
        assert eng.placed == HEADS_MAJOR and eng.loop_stats()["placed"] == HEADS_MAJOR
        assert eng.loop_stats()["start"]["placed"] == 3
        assert eng.params["wq"].format.layout.major_to_minor == (0, 2, 1, 3) and eng.params["wo"] is given["wo"]
        assert eng.submit([1], max_new_tokens=1).tokens()  # the loop's thread has run: it writes the start's one line
        said = [r.getMessage() for r in caplog.records if ", placed {" in r.getMessage()]  # the deployment's name is "placed" too
        assert len(said) == 1 and said[0].startswith("placed: ready ") and "'wq': (0, 2, 1, 3)" in said[0]
        prompt = [int(t) for t in TOKENS[:6]]
        want = np.asarray(G.generate(T.init_params(jax.random.PRNGKey(7), cfg), prompt, cfg, max_new_tokens=12))[0]
        assert eng.submit(prompt, max_new_tokens=12).tokens() == want.tolist()
        # placed tensors are committed to their device, and so is the pool from the start:
        # the bucket's prefill met one signature in its two calls, and was lowered once
        assert eng.submit(prompt, max_new_tokens=3).tokens() == want[:3].tolist()
        assert eng._prefill._cache_size() == 1
    finally:
        eng.shutdown()


def test_the_placing_program_never_comes_from_the_persistent_compile_cache(tmp_path, monkeypatch):
    """An executable read back from JAX's persistent cache has forgotten its
    result's layout: a tensor placed by it comes out labelled with the default
    layout over heads-major bytes, other weights to every reader (found on the
    chip, PR 32; the CPU backend does the same). So ``place_params`` compiles
    its copy every time, with a cache configured and warm as a replica's is."""
    from jax.experimental.compilation_cache import compilation_cache

    cfg = FORMS["gptj"]
    settings = {"jax_compilation_cache_dir": str(tmp_path), "jax_persistent_cache_min_compile_time_secs": 0.0,
                "jax_persistent_cache_min_entry_size_bytes": 0}
    before = {name: getattr(jax.config, name) for name in settings}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        for name, value in settings.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
        for start in range(2):  # a cold cache, then what the first start left in it
            jax.clear_caches()  # a new process: nothing compiled is in memory
            plain = T.init_params(jax.random.PRNGKey(5), cfg)
            placed, what = paged.place_params(G, cfg, T.init_params(jax.random.PRNGKey(5), cfg))
            assert what == HEADS_MAJOR
            for name in HEADS_MAJOR:
                assert placed[name].format.layout.major_to_minor == (0, 2, 1, 3)
                np.testing.assert_array_equal(placed[name], plain[name])
        assert jax.config.jax_enable_compilation_cache  # and the cache is on again for the programs behind it
    finally:
        for name, value in before.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()


# -- (f) the flat K/V pool's one owner, apart from any kind --------------------------------


def flat_step(positions, live, tables, block):
    """``paged.Step`` as ``forward_paged`` makes it, over ``positions`` and
    ``live`` (B, S) and ``tables`` (B, MB)."""
    b, n = positions.shape
    held = np.take_along_axis(tables, np.clip(positions // block, 0, tables.shape[1] - 1), axis=1)
    slots, flat = (held * block + positions % block).reshape(-1), live.reshape(-1)
    return paged.Step(
        jnp.asarray(positions), jnp.asarray(tables), block, jnp.asarray(np.where(flat, slots, np.arange(b * n) % block)),
        jnp.asarray(flat), jnp.asarray(np.where(live[:, 0], positions[:, 0] + 1, 0)))


@pytest.mark.parametrize("rows", [(2, 64), (1, 128)], ids=["a_head_a_row", "two_heads_a_row"])
@pytest.mark.parametrize("bucket", [8, 6], ids=["whole_blocks", "a_part_block"])
def test_the_flat_pool_writes_a_prompt_and_scores_the_next_step_as_dense_attention_does(bucket, rows):
    """``write_rows`` over two prompts (a call each, as a prefill makes them;
    the second shorter than its bucket), then ``attend`` for the decode step at
    each one's next position, in slots 0 and 2 of 3 with blocks out of order:
    every live output is dense causal attention's last row over the same keys
    and values, the empty slot's is zero, and the step's own row lies in the
    pool. A prompt of whole blocks goes a block a span and one written a
    position a call goes by row: behind the mask the two pools are the same.
    Both forms of a row: a K/V head of 64 a row, and LFM2's two heads to a row
    of 128. The CPU path (``paged_gather``)."""
    kv_heads, wide = rows
    H, G, d, layer, layers = 4, 2, 64, 1, 2
    assert kv_heads * wide == G * d
    rng = np.random.default_rng(bucket + wide)
    lengths, tables = [bucket, bucket - 1], np.array([[5, 2, 7], [0, 0, 0], [3, 6, 1]], np.int32)
    k, v = (rng.standard_normal((2, bucket + 1, G, d)).astype(np.float32) for _ in range(2))
    q = rng.standard_normal((3, 1, H, d)).astype(np.float32)
    spans = by_row = flat_kv.init_pool(layers, BLOCKS, BLOCK, kv_heads, wide, jnp.float32)
    assert spans.shape == (layers, 2, BLOCKS * BLOCK * kv_heads, wide)
    assert flat_kv.block_bytes(layers, BLOCK, kv_heads, wide, jnp.float32) * BLOCKS == spans.nbytes
    for i, slot in enumerate((0, 2)):
        positions, table = np.arange(bucket)[None, :], tables[slot:slot + 1]
        prompt = flat_step(positions, positions < lengths[i], table, BLOCK)
        spans = flat_kv.write_rows(spans, layer, prompt, k[i:i + 1, :bucket], v[i:i + 1, :bucket], kv_heads)
        for t in range(lengths[i]):
            one = flat_step(np.array([[t]]), np.array([[True]]), table, BLOCK)
            by_row = flat_kv.write_rows(by_row, layer, one, k[i:i + 1, t:t + 1], v[i:i + 1, t:t + 1], kv_heads)
    step = flat_step(np.array([[lengths[0]], [0], [lengths[1]]]), np.array([[True], [False], [True]]), tables, BLOCK)
    *held, mask = flat_kv.gather_rows(spans, layer, step._replace(lengths=step.lengths - 1), kv_heads)
    assert mask.sum(axis=1).tolist() == [lengths[0], 0, lengths[1]]
    for mine, theirs in zip(held, flat_kv.gather_rows(by_row, layer, step, kv_heads)[:2]):
        np.testing.assert_array_equal(np.where(mask[..., None, None], mine, 0), np.where(mask[..., None, None], theirs, 0))
    assert not np.any(spans[0]) and not np.any(by_row[0])  # the other layer's rows are left alone
    new_k, new_v = (np.stack([t[0, lengths[0]], np.zeros((G, d), np.float32), t[1, lengths[1]]])[:, None] for t in (k, v))
    o, after = flat_kv.attend(spans, layer, step, jnp.asarray(q), jnp.asarray(new_k), jnp.asarray(new_v), kv_heads=kv_heads)
    assert o.shape == (3, 1, H, d) and not np.any(o[1])
    keys, values, mask = flat_kv.gather_rows(after, layer, step, kv_heads)
    for i, slot in enumerate((0, 2)):
        n = lengths[i] + 1
        mine = jnp.asarray(np.repeat(q[slot], n, axis=0)[None])  # the step's query at every position: the last row is the step's
        want = attention(mine, jnp.asarray(k[i:i + 1, :n]), jnp.asarray(v[i:i + 1, :n]), causal=True)
        np.testing.assert_allclose(o[slot, 0], want[0, -1], rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(keys[slot, :n].reshape(n, G, d), k[i, :n])
        np.testing.assert_array_equal(values[slot, :n].reshape(n, G, d), v[i, :n])
        assert mask[slot].sum() == n
