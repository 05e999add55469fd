"""Training's attention kernels (``ops.attention._flash``'s custom VJP over
``ops/flash_kernels.py``'s forward kernel and its one-pass backward kernel) in
Pallas interpret mode on the CPU, against the einsum path. Their compile for the chip is in ``test_tpu_compile.py``; their
speed is the benchmark's."""

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

import ray_tpu.ops  # noqa: F401
from ray_tpu.models import transformer
from ray_tpu.ops import flash_kernels

A = sys.modules["ray_tpu.ops.attention"]  # ray_tpu.ops.attention is the function

FORWARD_KERNEL = "flash_attention_fwd"
BACKWARD_KERNEL = "flash_mha_bwd"


def _qkv(seq, head_dim, seed=0, batch=1, heads=2):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return tuple(
        jax.random.normal(k, (batch, seq, heads, head_dim), jnp.float32).astype(jnp.bfloat16)
        for k in keys
    )


def _close(got, want, what):
    """bfloat16's tolerance: eight bits of mantissa on the largest element."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.abs(got - want).max() <= 2.0 ** -6 * np.abs(want).max(), what


def _layer(attend, x, w):
    """A block's attention as ``transformer._block`` has it: projections with
    no batch dimension (what "dots" keeps), the kernel, the output's product."""
    q, k, v = (jnp.einsum("bsd,dhk->bshk", x, w[name]) for name in ("wq", "wk", "wv"))
    return x + jnp.einsum("bshk,hkd->bsd", attend(q, k, v, causal=True), w["wo"])


def _weights(d_model, heads, head_dim, seed):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    shapes = {"wq": (d_model, heads, head_dim), "wk": (d_model, heads, head_dim),
              "wv": (d_model, heads, head_dim), "wo": (heads, head_dim, d_model)}
    return {
        name: (jax.random.normal(k, shape, jnp.float32) * d_model ** -0.5).astype(jnp.bfloat16)
        for k, (name, shape) in zip(keys, shapes.items())
    }


def _kernel_calls(jaxpr, recomputed=False, found=None):
    """[(kernel's name, inside a ``jax.checkpoint``'s recomputation)] of every
    ``pallas_call`` in ``jaxpr``, sub-jaxprs included."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params["name"] or eqn.params["jaxpr"].debug_info.func_name, recomputed))
        inside = recomputed or eqn.primitive.name in ("checkpoint", "remat2")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_calls(sub, inside, found)
    return found


CASES = [
    ("plain", 128, 256), ("plain", 128, 512), ("plain", 256, 256), ("plain", 256, 512),
    ("dots", 128, 256), ("dots", 256, 512),
    ("count", 128, 256), ("count_full", 128, 256),
]


@pytest.fixture
def interpreted(monkeypatch):
    """Every ``pallas_call`` made while the test runs is interpreted: the
    library's kernels take no ``interpret`` of their own, and the TPU
    interpreter's callbacks are effects that ``jax.checkpoint`` refuses."""
    compiled = pl.pallas_call
    monkeypatch.setattr(pl, "pallas_call", lambda *a, **kw: compiled(*a, **{**kw, "interpret": True}))


@pytest.mark.parametrize("what,head_dim,seq", CASES, ids=[f"{w}-{d}-{s}" for w, d, s in CASES])
def test_flash_custom_vjp(interpreted, what, head_dim, seq):
    """``plain``: output and the three gradients of ``_flash`` against
    ``_einsum_attention``. ``dots``: the same through two layers under
    ``jax.checkpoint`` with the policy ``transformer.forward`` builds.
    ``count``: in ``jax.grad``'s jaxpr of those two layers the forward kernel
    runs once a layer, none of them in a recomputation, beside one backward
    kernel a layer; ``count_full``: full recomputation runs it again."""
    flash = A._flash
    if what == "plain":
        q, k, v, do = _qkv(seq, head_dim)

        def run(attend):
            out, vjp = jax.vjp(lambda q, k, v: attend(q, k, v, causal=True), q, k, v)
            return (out, *vjp(do))

        for name, got, want in zip(("o", "dq", "dk", "dv"), run(flash), run(A._einsum_attention)):
            _close(got, want, name)
        return

    d_model, heads = 256, 2
    ws = [_weights(d_model, heads, head_dim, seed) for seed in (1, 2)]
    x = jax.random.normal(jax.random.PRNGKey(0), (1, seq, d_model), jnp.float32).astype(jnp.bfloat16)
    policy = "dots" if what != "count_full" else None

    def loss(ws, x, attend):
        for w in ws:
            x = transformer._recomputed(functools.partial(_layer, attend), policy)(x, w)
        return jnp.sum(x.astype(jnp.float32) ** 2)

    if what == "dots":
        got = jax.grad(loss)(ws, x, flash)
        want = jax.grad(loss)(ws, x, A._einsum_attention)
        for layer, (g, w) in enumerate(zip(got, want)):
            for name in g:
                _close(g[name], w[name], f"layer {layer} {name}")
        return

    calls = _kernel_calls(jax.make_jaxpr(jax.grad(functools.partial(loss, attend=flash)))(ws, x).jaxpr)
    forward = [again for name, again in calls if name == FORWARD_KERNEL]
    backward = [again for name, again in calls if name == BACKWARD_KERNEL]
    assert len(forward) + len(backward) == len(calls), calls
    assert len(backward) == 2
    if what == "count":
        assert forward == [False, False], calls  # once a layer, in the primal
    else:
        assert sorted(forward) == [False, False, True, True], calls


@pytest.mark.parametrize("n_layers", [2, transformer.UNROLLED_LAYERS + 1], ids=["unrolled", "rolled"])
def test_the_models_loop_keeps_the_forward_kernels_results_under_dots(interpreted, monkeypatch, n_layers):
    """``transformer.loss_fn``'s gradient with ``remat_policy="dots"``, the
    layers' loop laid out whole and rolled: the loop that goes forward holds
    the forward kernel and the one that goes back holds the backward kernel
    beside no forward kernel, in its recomputation or out of it (what
    ``FLASH_RESIDUALS`` names was kept)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the branch ``attention`` takes on the chip
    cfg = transformer.TransformerConfig(
        vocab_size=128, d_model=256, n_layers=n_layers, n_heads=2, d_ff=256, max_seq_len=128,
        parallel_block=True, use_swiglu=False, remat_policy="dots",
    )
    params = jax.eval_shape(lambda: transformer.init_params(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(functools.partial(transformer.loss_fn, cfg=cfg)))(params, tokens, tokens).jaxpr
    loops = [eqn for eqn in jaxpr.eqns if eqn.primitive.name == "scan"]
    assert [eqn.params["unroll"] for eqn in loops] == [n_layers if n_layers <= transformer.UNROLLED_LAYERS else 1] * 2
    forward, backward = (_kernel_calls(eqn.params["jaxpr"].jaxpr) for eqn in loops)
    assert forward == [(FORWARD_KERNEL, False)]
    assert [name for name, _ in backward] == [BACKWARD_KERNEL]  # beside the block's recomputation, which runs no kernel
    assert _kernel_calls(jaxpr) == forward + backward


@pytest.mark.parametrize("block_q,block_k,causal", [(128, 256, True), (256, 128, True), (128, 128, False)])
def test_backward_kernel_blocks(block_q, block_k, causal):
    """The one-pass backward kernel alone, at block shapes that differ and
    without the mask, against the einsum path's gradients."""
    seq, head_dim = 512, 128
    q, k, v, do = _qkv(seq, head_dim, seed=3)
    out, vjp = jax.vjp(lambda q, k, v: A._einsum_attention(q, k, v, causal=causal), q, k, v)
    want = vjp(do)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * head_dim ** -0.5
    if causal:
        scores = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), scores, -1e30)
    lse = jax.nn.logsumexp(scores, axis=-1)
    di = jnp.swapaxes(jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32), axis=-1), 1, 2)
    got = flash_kernels.flash_attention_bwd(
        *A._heads_major(q, k, v, do), lse, di, causal=causal, sm_scale=head_dim ** -0.5,
        block_q=block_q, block_k=block_k, interpret=True,
    )
    for name, g, w in zip(("dq", "dk", "dv"), A._heads_major(*got), want):
        _close(g, w, name)
