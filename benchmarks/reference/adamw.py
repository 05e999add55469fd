"""Plain reference of the optimizer's first step: AdamW from zero moments, in
float64 numpy, on the entries handed in. No import from the program or from
optax. After one step from zero moments the first moment is (1 - b1) x the
gradient and the second (1 - b2) x its square, so the moments the program's
step leaves behind are its own gradients, read through its own program."""

from __future__ import annotations

import numpy as np


def first_step(p, g, *, learning_rate, b1, b2, eps, weight_decay, param_dtype, moment_dtype=None):
    """``p`` the parameters before the step, ``g`` their gradients (arrays of
    one shape). Returns (mu, nu, new_p) in float64. ``new_p`` is rounded to
    ``param_dtype``, the type the configuration keeps the parameters in: no
    step can hold more. ``moment_dtype`` None keeps the moments unrounded (the
    reference); a type rounds them as a program that stores them so would."""
    p, g = np.asarray(p, np.float64), np.asarray(g, np.float64)
    mu, nu = (1 - b1) * g, (1 - b2) * g * g
    if moment_dtype is not None:
        mu = mu.astype(moment_dtype).astype(np.float64)
        nu = nu.astype(moment_dtype).astype(np.float64)
    m_hat, v_hat = mu / (1 - b1), nu / (1 - b2)
    update = -learning_rate * (m_hat / (np.sqrt(v_hat) + eps) + weight_decay * p)
    return mu, nu, (p + update).astype(param_dtype).astype(np.float64)
