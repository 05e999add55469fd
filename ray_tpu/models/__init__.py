"""Model zoo: decoder-only LMs (GPT-J/Llama families), MNIST nets, MoE.

These play the role of the reference's example/benchmark workloads
(``release/train_tests``, ``rllib/tuned_examples``) but are first-class here:
every model declares logical sharding axes so it runs under any mesh.
"""

from ray_tpu.models.transformer import (
    TransformerConfig,
    forward,
    init_params,
    loss_fn,
    param_logical_axes,
)

__all__ = [
    "TransformerConfig",
    "init_params",
    "forward",
    "loss_fn",
    "param_logical_axes",
]

from ray_tpu.models import vit  # noqa: E402  (ViT family: models/vit.py)

__all__.append("vit")


def paged_model(cfg):
    """The module that runs ``cfg`` over a paged pool for ``serve.llm``:
    ``make_paged_fns``, ``init_paged_pool``, ``paged_block_bytes`` and
    ``init_params``. The pool is the model's to shape; the engine asks here."""
    from ray_tpu.models import generation, longcat

    return longcat if isinstance(cfg, longcat.LongcatConfig) else generation


__all__.append("paged_model")
