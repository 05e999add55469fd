"""A model family is all the harness knows of a model. A configuration file
may state ``"family": "<name>"``; ``benchmarks/families/<name>.py`` is then a
plain module with these names (``README.md``, "Adding a family"):

    model_kwargs(config) -> dict         the program's model arguments from the
                                         file's published keys; holds
                                         ``vocab_size`` and ``dtype``
    train_config(model)                  what ``build_lm_train_step`` takes
    make_weights(words, model, dtype)    traceable; the program's parameter
                                         layout from ``seed_words(seed)``
    reference()                          the module ``reference/<name>.py``:
                                         ``logits_at`` and, for training,
                                         ``mean_loss_and_grads``
    weight_count(model)                  parameters a decode step reads
    decode_step_need(model, batch, live_rows, itemsize)
                                         bytes and FLOPs of one decode step

No class, no registry, no fallback between families: a name that has no
module is an ImportError. A family module imports nothing heavy at its top
(the parent, which must stay off JAX, imports it for ``model_kwargs``).
"""

from __future__ import annotations

import importlib

# the family of a configuration that states none: the two configurations the
# benchmark started with stay byte for byte as they are
DEFAULT = "gptj"


def of(config: dict):
    """The family module a configuration names."""
    return importlib.import_module(f"{__name__}.{config.get('family', DEFAULT)}")
