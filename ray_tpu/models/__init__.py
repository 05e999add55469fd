"""Model zoo: decoder-only LMs (GPT-J/Llama families), MNIST nets, MoE.

These play the role of the reference's example/benchmark workloads
(``release/train_tests``, ``rllib/tuned_examples``) but are first-class here:
every model declares logical sharding axes so it runs under any mesh.
"""

import importlib

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


# The model kinds ``serve.llm`` runs: the ``kind`` a config dict names (None:
# it names none) -> (its module, the name of its config class there). The module
# gives ``models/paged.py`` a kind's four things; it is imported when asked for.
# Ten: GPT-J/Llama, LongCat and Kimi-K2 (latent attention, routed experts),
# Olmo-Hybrid (gated delta rule), Phi-4-mini-flash (Mamba-1, window rings),
# K-EXAONE (window rings, sigmoid experts), Falcon-H1 (Mamba-2 beside attention),
# LFM2 (short convolutions, experts all held), Granite 4.0-H (Mamba-2 nine layers
# in ten, softmax-over-the-chosen experts in every layer), Nemotron-H (layers of
# one part: a Mamba-2 mixer, attention or experts of two matrices in a latent;
# ``models/mamba2.py`` is the mixer it shares with Falcon-H1 and Granite).
PAGED_KINDS = {
    None: ("ray_tpu.models.generation", "TransformerConfig"),
    "longcat": ("ray_tpu.models.longcat", "LongcatConfig"),
    "kimi_k2": ("ray_tpu.models.kimi", "KimiConfig"),
    "olmo_hybrid": ("ray_tpu.models.olmo_hybrid", "OlmoHybridConfig"),
    "phi4flash": ("ray_tpu.models.phi4flash", "Phi4FlashConfig"),
    "exaone_moe": ("ray_tpu.models.exaone_moe", "ExaoneMoeConfig"),
    "falcon_h1": ("ray_tpu.models.falcon_h1", "FalconH1Config"),
    "lfm2_moe": ("ray_tpu.models.lfm2_moe", "Lfm2MoeConfig"),
    "granite_hybrid": ("ray_tpu.models.granite_hybrid", "GraniteHybridConfig"),
    "nemotron_h": ("ray_tpu.models.nemotron_h", "NemotronHConfig"),
}


def paged_config(kind):
    """The config class of the model kind a config dict names."""
    if kind not in PAGED_KINDS:
        raise ValueError(f"unknown model kind {kind!r} (known: {sorted(k for k in PAGED_KINDS if k)})")
    module, config = PAGED_KINDS[kind]
    return getattr(importlib.import_module(module), config)


def paged_model(cfg):
    """The module of ``cfg``'s model kind. The pool is the kind's to shape;
    the engine asks here."""
    for module, config in PAGED_KINDS.values():
        if type(cfg).__name__ == config:
            return importlib.import_module(module)
    raise TypeError(f"{type(cfg).__name__} is the config of no model kind in PAGED_KINDS")


__all__ += ["PAGED_KINDS", "paged_config", "paged_model"]
