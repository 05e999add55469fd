"""Pipeline parallelism, MoE expert parallelism, MNIST models (CPU mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.parallel.mesh import MeshConfig, create_mesh


def test_pipeline_matches_sequential(cpu_mesh_devices):
    from ray_tpu.parallel.pipeline import make_pipeline_fn

    mesh = create_mesh(MeshConfig(pipeline=4, data=2))
    P_stages, M, mb, d = 4, 8, 4, 16

    def stage_fn(params, x):
        return jnp.tanh(x @ params["w"] + params["b"])

    key = jax.random.PRNGKey(0)
    stacked = {
        "w": jax.random.normal(key, (P_stages, d, d)) * 0.5,
        "b": jnp.zeros((P_stages, d)),
    }
    microbatches = jax.random.normal(jax.random.fold_in(key, 1), (M, mb, d))
    ref = microbatches
    for s in range(P_stages):
        ref = jnp.tanh(ref @ stacked["w"][s] + stacked["b"][s])

    pipe = make_pipeline_fn(stage_fn, mesh)
    sharded = jax.device_put(stacked, NamedSharding(mesh, P("pipeline")))
    out = jax.jit(pipe)(sharded, microbatches)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_moe_expert_parallel_matches_single(cpu_mesh_devices):
    from ray_tpu.models.moe import expert_layer, expert_param_logical_axes, init_expert_params
    from ray_tpu.parallel.sharding import DEFAULT_LM_RULES, infer_param_sharding

    # 8 routed experts, all held, and 4 identity experts; top-3 of 12 outputs
    params = init_expert_params(jax.random.PRNGKey(0), 32, 64, held=8, n_outputs=12)
    params["router_bias"] = 0.01 * jax.random.normal(jax.random.PRNGKey(2), (12,))
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 32))
    kw = dict(n_routed=8, top_k=3, scale=6.0)
    y_ref, counts_ref = expert_layer(params, x, **kw)

    mesh = create_mesh(MeshConfig(expert=8))
    shardings = infer_param_sharding(expert_param_logical_axes(), DEFAULT_LM_RULES, mesh)
    params_sh = jax.tree.map(lambda p, s: jax.device_put(p, s), params, shardings)
    y_ep, counts_ep = jax.jit(lambda p, xx: expert_layer(p, xx, **kw))(params_sh, x)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(counts_ep), np.asarray(counts_ref))


def test_moe_routes_every_row_whatever_the_load():
    from ray_tpu.models.moe import expert_layer, init_expert_params

    # what was the capacity overflow case: 32 tokens, 2 experts, top-1. There
    # is no capacity any more: every row reaches its expert and none is zero
    params = init_expert_params(jax.random.PRNGKey(0), 16, 32, held=2, n_outputs=2)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    y, counts = expert_layer(params, x, n_routed=2, top_k=1, scale=1.0)
    assert np.asarray(counts).tolist()[:3] == [32, 0, 0]
    assert np.all(np.isfinite(np.asarray(y))) and np.all(np.abs(np.asarray(y)).sum(-1) > 0)


def test_mnist_mlp_learns_synthetic(cpu_mesh_devices):
    import optax

    from ray_tpu.models.mnist import accuracy, apply_mlp, cross_entropy_loss, init_mlp
    from ray_tpu.parallel.sharding import batch_sharding

    mesh = create_mesh(MeshConfig(data=8))
    rng = np.random.default_rng(0)
    # synthetic separable data: class = argmax of 10 fixed projections
    w_true = rng.normal(size=(784, 10))
    xs = rng.normal(size=(512, 784)).astype(np.float32)
    ys = np.argmax(xs @ w_true, axis=1).astype(np.int32)

    params = init_mlp(jax.random.PRNGKey(0), hidden=(64,))
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, x, y):
        def loss(p):
            return cross_entropy_loss(apply_mlp(p, x), y)

        lval, grads = jax.value_and_grad(loss)(params)
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, lval

    sh = batch_sharding(mesh)
    xd = jax.device_put(xs, sh)
    yd = jax.device_put(ys, sh)
    first = None
    for i in range(30):
        params, opt_state, lval = step(params, opt_state, xd, yd)
        # sync every step: queuing many async 8-way collectives starves the
        # XLA-CPU rendezvous on a 1-core host and aborts the process
        lval = float(lval)
        first = first if first is not None else lval
    assert lval < first * 0.6
    acc = float(accuracy(apply_mlp(params, xd), yd))
    assert acc > 0.5


def test_mnist_cnn_shapes():
    from ray_tpu.models.mnist import apply_cnn, init_cnn

    params = init_cnn(jax.random.PRNGKey(0))
    x = jnp.ones((2, 28, 28, 1))
    logits = apply_cnn(params, x)
    assert logits.shape == (2, 10)


def test_kv_cache_generation_matches_full_forward(cpu_mesh_devices):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.generation import generate
    from ray_tpu.models.transformer import TransformerConfig, forward, init_params

    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=64, remat=False, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(1), cfg)
    prompt = np.array([[5, 9, 3, 7, 2], [1, 2, 3, 4, 6]], dtype=np.int32)
    toks = np.asarray(generate(params, prompt, cfg, max_new_tokens=5))
    cur = prompt
    for step in range(5):
        logits = forward(params, jnp.asarray(cur), cfg)
        nxt = np.argmax(np.asarray(logits[:, -1, :], dtype=np.float32), axis=-1)
        assert (toks[:, step] == nxt).all(), f"divergence at step {step}"
        cur = np.concatenate([cur, nxt[:, None].astype(np.int32)], axis=1)


@pytest.mark.parametrize("remat_policy", ["dots", "full"])
@pytest.mark.parametrize("n_layers", [2, 4])
def test_the_layers_loop_gives_the_same_loss_and_gradients_unrolled_and_rolled(monkeypatch, n_layers, remat_policy):
    """``forward`` lays a shallow stack's loop out whole (``UNROLLED_LAYERS``)
    and keeps a deep one's rolled: the same products in the same types either
    way, so the loss and every gradient agree to float32's rounding of a
    re-ordered sum (the compiler fuses the two programs differently)."""
    from ray_tpu.models import transformer

    cfg = transformer.TransformerConfig(
        vocab_size=128, d_model=64, n_layers=n_layers, n_heads=4, d_ff=128, max_seq_len=32,
        parallel_block=True, use_swiglu=False, remat_policy=remat_policy, dtype=jnp.float32,
    )
    params = transformer.init_params(jax.random.PRNGKey(2), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 32), 0, cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)

    def step():
        f = jax.jit(jax.value_and_grad(lambda p: transformer.loss_fn(p, tokens, targets, cfg)))
        return f.lower(params).as_text().count("stablehlo.while"), f(params)

    assert n_layers <= transformer.UNROLLED_LAYERS
    whiles, (loss, grads) = step()
    assert whiles == 0  # every layer stands in the program
    monkeypatch.setattr(transformer, "UNROLLED_LAYERS", n_layers - 1)
    whiles, (rolled_loss, rolled_grads) = step()
    assert whiles == 2  # the forward loop and the backward loop
    np.testing.assert_allclose(loss, rolled_loss, rtol=1e-6)
    assert sorted(grads) == sorted(params)
    for name in grads:
        # the parallel block has no norm of the MLP's own: its gradient is zero
        assert name == "mlp_norm" or np.abs(np.asarray(grads[name])).max() > 0, name
        np.testing.assert_allclose(grads[name], rolled_grads[name], rtol=1e-4, atol=1e-7, err_msg=name)


def test_vit_forward_and_grads():
    """ViT family: forward shapes, fp32 logits, grads flow."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import vit

    cfg = vit.VIT_TINY_TEST
    params = vit.init_params(jax.random.PRNGKey(0), cfg)
    images = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3))
    logits = jax.jit(lambda p, x: vit.forward(cfg, p, x))(params, images)
    assert logits.shape == (4, 10) and logits.dtype == jnp.float32

    labels = jnp.array([0, 1, 2, 3])
    (loss, acc), grads = jax.value_and_grad(
        lambda p: vit.loss_fn(cfg, p, images, labels), has_aux=True
    )(params)
    assert jnp.isfinite(loss)
    gnorm = jax.tree_util.tree_reduce(
        lambda a, g: a + jnp.sum(jnp.abs(g.astype(jnp.float32))), grads, 0.0
    )
    assert gnorm > 0


def test_vit_patchify_roundtrip():
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import vit

    cfg = vit.ViTConfig(image_size=4, patch_size=2, num_channels=1,
                        d_model=8, n_layers=1, n_heads=1, d_ff=8)
    img = jnp.arange(16, dtype=jnp.float32).reshape(1, 4, 4, 1)
    patches = vit.patchify(cfg, img)
    assert patches.shape == (1, 4, 4)
    # first patch = top-left 2x2 block in row-major order
    np.testing.assert_array_equal(np.asarray(patches[0, 0]), [0, 1, 4, 5])


def test_vit_sharded_train_step_on_mesh():
    """ViT under DP+TP GSPMD sharding on the virtual mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models import vit
    from ray_tpu.parallel.mesh import create_mesh

    from ray_tpu.parallel.sharding import (
        DEFAULT_LM_RULES,
        batch_sharding,
        shard_params,
    )

    if len(jax.devices()) < 4:
        import pytest

        pytest.skip("needs the virtual multi-device mesh")
    mesh = create_mesh(data=-1, tensor=2, drop_trivial_axes=True)
    cfg = vit.VIT_TINY_TEST
    params = vit.init_params(jax.random.PRNGKey(0), cfg)
    params = shard_params(
        params, vit.param_logical_axes(cfg), DEFAULT_LM_RULES, mesh
    )
    opt = optax.sgd(1e-2)
    opt_state = opt.init(params)

    batch_shard = batch_sharding(mesh)

    @jax.jit
    def step(params, opt_state, images, labels):
        (loss, acc), grads = jax.value_and_grad(
            lambda p: vit.loss_fn(cfg, p, images, labels), has_aux=True
        )(params)
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    images = jax.device_put(
        np.random.RandomState(0).randn(8, 32, 32, 3).astype(np.float32),
        batch_shard,
    )
    labels = jax.device_put(np.arange(8) % 10, batch_shard)
    losses = []
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, images, labels)
        losses.append(float(loss))
    assert losses[-1] < losses[0]  # it optimizes
