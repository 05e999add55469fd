"""The plain reference against ``ray_tpu.models`` at sizes a test can hold,
and the control of `correct`: the reference computed in int8 has to read far
worse than the program does, or no limit could tell them apart."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import gptj as family
from benchmarks.harness.weights import seed_words
from ray_tpu.models import generation as G
from ray_tpu.models import transformer as tfm

gptj, make_weights = family.reference(), family.make_weights
MODEL = dict(vocab_size=1024, d_model=256, n_layers=4, n_heads=4, d_ff=1024, max_seq_len=256,
             parallel_block=True, use_swiglu=False, tie_embeddings=False)


def setup(dtype, seed=2**31 + 3):
    cfg = tfm.TransformerConfig(**MODEL, dtype=dtype)
    params = jax.jit(lambda w: make_weights(w, MODEL, dtype))(seed_words(seed))
    return cfg, params


def rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def paged_logits(cfg, params, prompt, fed, block_size=4, blocks=64):
    """Prefill ``prompt`` then decode ``fed`` one token a step through the
    paged cache; the logits of every position fed."""
    prefill, decode, _ = G.make_paged_fns(cfg, block_size=block_size)
    pool = G.init_paged_pool(cfg, blocks, block_size)
    n_blocks = -(-(len(prompt) + len(fed)) // block_size)
    table = np.zeros((1, 32), np.int32)
    table[0, :n_blocks] = np.arange(1, n_blocks + 1)
    toks = np.zeros((1, 64), np.int32)
    toks[0, : len(prompt)] = prompt
    logits, pool = prefill(params, jnp.asarray(toks), jnp.asarray(table), pool, jnp.int32(len(prompt)))
    out = [np.asarray(logits[0])]
    for i, t in enumerate(fed):
        logits, pool = decode(params, jnp.asarray([t], jnp.int32), jnp.asarray([len(prompt) + i], jnp.int32),
                              jnp.asarray(table), pool, jnp.asarray([True]))
        out.append(np.asarray(logits[0]))
    return np.stack(out)


def sequence(n, seed=1):
    return np.random.default_rng(seed).integers(1, MODEL["vocab_size"] - 1, n).tolist()


def test_prefill_then_decode_through_the_paged_cache_agrees_with_the_reference():
    cfg, params = setup(jnp.float32)
    prompt, fed = sequence(37), sequence(9, seed=2)
    got = paged_logits(cfg, params, prompt, fed)
    rows = np.arange(len(prompt) - 1, len(prompt) + len(fed))
    padded = np.zeros(64, np.int32)
    padded[: len(prompt) + len(fed)] = prompt + fed
    want = gptj.logits_at(params, padded, rows)
    assert rel(got, want) < 1e-5


def test_the_block_cut_at_its_contractions_is_the_block():
    _, params = setup(jnp.float32)
    x = gptj._embed(params, jnp.asarray(sequence(24)), "f32")
    whole = gptj.block(x, {k: params[k][1] for k in gptj.LAYER_KEYS}, "f32")
    assert rel(gptj.block_by_tensor(x, params, 1, "f32"), whole) < 1e-6


def test_loss_and_gradients_agree_with_the_program():
    cfg, params = setup(jnp.float32)
    tokens = jnp.asarray(np.stack([sequence(128, s) for s in (3, 4)]), jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    loss, grads = jax.value_and_grad(lambda p: tfm.loss_fn(p, tokens, targets, cfg))(params)
    ref_loss, ref_grads = gptj.loss_and_grads(params, tokens, targets, leaves=("attn_norm", "final_norm", "wq", "w_down"))
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) < 1e-6
    mean_loss, mean_grads = gptj.mean_loss_and_grads(params, tokens, targets, leaves=("attn_norm", "wq"))
    assert mean_loss == pytest.approx(float(ref_loss), rel=1e-6)  # a sequence a call is the batch
    assert rel(mean_grads["wq"], ref_grads["wq"]) < 1e-5
    for k, g in ref_grads.items():
        assert rel(grads[k], g) < 1e-4, k


@pytest.mark.parametrize("seed", [5, 2**31 + 9, 3_000_000_019])
def test_the_control_reads_far_worse_than_the_bf16_program(seed):
    """Serving, at the test's size: the program in bfloat16 (the type the
    configuration states) against the reference, and the reference in fp8 in
    the program's place: at least three times worse, so a limit between the
    two holds. int8 weights read about 2.5 times worse: worse at every seed,
    but too close for the rule of three, so they are not the control."""
    cfg, params = setup(jnp.bfloat16, seed)
    prompt, fed = sequence(40, seed % 97), sequence(8, seed % 89)
    rows = np.arange(len(prompt) - 1, len(prompt) + len(fed))
    padded = np.zeros(64, np.int32)
    padded[: len(prompt) + len(fed)] = prompt + fed
    want = np.asarray(gptj.logits_at(params, padded, rows))
    program = max(rel(g, w) for g, w in zip(paged_logits(cfg, params, prompt, fed), want))
    control = min(rel(c, w) for c, w in zip(np.asarray(gptj.logits_at(params, padded, rows, "fp8")), want))
    int8 = min(rel(c, w) for c, w in zip(np.asarray(gptj.logits_at(params, padded, rows, "int8")), want))
    assert control > 3 * program and int8 > 1.5 * program, (program, int8, control)


ADAMW = {"b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 0.01}
LEAVES = ("attn_norm", "final_norm", "wq")


def first_step_readings(dtype, seed, controls=(), spoil=None):
    """The training cells' `correct` at the test's size: ``step_fn`` from the
    seeded weights on one batch, sampled and compared as ``train_cell`` does.
    ``spoil`` changes what the step left behind before it is compared."""
    from benchmarks.harness import stepcheck
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.parallel.spmd import build_lm_train_step

    cfg, params = setup(dtype, seed)
    bundle = build_lm_train_step(cfg, create_mesh(MeshConfig(data=-1), devices=jax.devices()[:1]), learning_rate=1e-4)
    state = bundle.init_state(0)
    state["params"] = jax.device_put(params, bundle.param_shardings)
    tokens = jnp.asarray(np.stack([sequence(128, s) for s in (3, 4, 5, 6)]), jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    picks = stepcheck.draw_picks(seed, state["params"], LEAVES, 512)
    take = stepcheck.make_take()
    before = take(state["params"], picks)
    state, metrics = bundle.step_fn(state, *bundle.shard_batch(np.asarray(tokens), np.asarray(targets)))
    sampled = stepcheck.sample_step(take, state, picks, before)
    if spoil:
        spoil(sampled)
    _, fresh = setup(dtype, seed)
    return stepcheck.compare(gptj, sampled, float(metrics["loss"]), fresh, tokens, targets, picks, ADAMW, 1e-4, controls)


def test_the_first_step_agrees_with_the_plain_reference_in_float32():
    r = first_step_readings(jnp.float32, 11)
    assert r["step_loss_rel_err"] < 1e-6
    for k in LEAVES:
        assert r[f"step_mu_rel_err.{k}"] < 1e-3 and r[f"step_nu_rel_err.{k}"] < 2e-3, (k, r)
    # AdamW's first step is a step of learning_rate against the gradient's sign: the
    # few entries whose gradient is too small to have a sure sign are all that differ
    assert r["step_update_rel_err.attn_norm"] < 0.05 and r["step_update_rel_err.wq"] < 0.05


@pytest.mark.parametrize("seed", [5, 2**31 + 9, 3_000_000_019])
def test_the_control_fails_the_step_comparison_too(seed):
    """The bfloat16 step against the reference, and the reference in fp8 and
    int8 in the step's place: the first moment of ``attn_norm`` (the step's own
    gradient, gathered over the whole backward pass) reads at least three
    times worse for either, so a limit between the two holds."""
    r = first_step_readings(jnp.bfloat16, seed, controls=("fp8", "int8"))
    program = r["step_mu_rel_err.attn_norm"]
    assert r["control_fp8"]["step_mu_rel_err.attn_norm"] > 3 * program, r
    assert r["control_int8"]["step_mu_rel_err.attn_norm"] > 2 * program, r


def skipped(s):
    s["after"] = dict(s["before"])


def moments_in_fp8(s):
    import ml_dtypes

    for name in ("mu", "nu"):
        s[name] = {k: v.astype(np.float32).astype(ml_dtypes.float8_e4m3fn).astype(np.float64)
                   for k, v in s[name].items()}


@pytest.mark.parametrize("spoil,number,floor", [
    (skipped, "step_update_rel_err.attn_norm", 0.99),  # an update that was not applied reads 1
    (moments_in_fp8, "step_mu_rel_err.attn_norm", 0.02),  # three bits of mantissa: ~2.5%
])
def test_a_spoiled_optimizer_half_is_seen(spoil, number, floor):
    sound = first_step_readings(jnp.float32, 11)[number]
    assert first_step_readings(jnp.float32, 11, spoil=spoil)[number] > max(floor, 3 * sound)


def test_weights_come_from_the_seed_and_one_program_serves_every_seed():
    fn = jax.jit(lambda w: make_weights(w, MODEL, jnp.float32))
    a, b, c = fn(seed_words(2**31 + 5)), fn(seed_words(2**31 + 5)), fn(seed_words(2**32 + 2**31 + 5))
    assert all(bool((a[k] == b[k]).all()) for k in a) and not bool((a["wq"] == c["wq"]).all())
    assert fn._cache_size() == 1  # the seed is an argument: no compile a seed
    assert abs(float(a["wq"].std()) * MODEL["d_model"] ** 0.5 - 1.0) < 0.02
