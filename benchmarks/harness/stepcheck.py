"""`correct` in the training cells: the measured program's own first step,
``bundle.step_fn`` from the seeded weights and zero moments on the first
batch, against the plain reference. What is compared is what that step left
behind: its loss, and a seeded sample of its first moments (its gradients),
its second moments, and the change it made to the parameters, each against a
plain float64 AdamW step (``reference/adamw.py``) of the float32 gradients
that the family's plain reference gives over the same whole batch."""

from __future__ import annotations

import math

import numpy as np


def draw_picks(seed: int, params: dict, leaves, n: int) -> dict:
    """``n`` seeded flat indices into each of the ``leaves``."""
    rng = np.random.default_rng([int(seed), 5])
    return {k: rng.integers(0, math.prod(params[k].shape), int(n)) for k in leaves}


def make_take():
    """One jitted gather for every seed: the indices are an argument (traced
    in as constants they would compile again for each seed)."""
    import jax

    return jax.jit(lambda tree, idx: {k: tree[k].reshape(-1)[i] for k, i in idx.items()})


def sample_step(take, state, picks: dict, before: dict) -> dict:
    """After the first step: the sampled parameters as they were (``before``,
    taken from the state ahead of the step) and as they are, and both
    moments, on the host in float64 with the parameters' own types noted."""
    import optax

    opt = state["opt"]
    got = {
        "before": before, "after": take(state["params"], picks),
        "mu": take(optax.tree_utils.tree_get(opt, "mu"), picks),
        "nu": take(optax.tree_utils.tree_get(opt, "nu"), picks),
    }
    out = {name: {k: np.asarray(v).astype(np.float64) for k, v in d.items()} for name, d in got.items()}
    out["dtype"] = {k: np.asarray(v).dtype for k, v in got["after"].items()}
    return out


def rel(a, b) -> float:
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def compare(reference, sampled: dict, step_loss: float, params1, tokens, targets, picks: dict, adamw: dict,
            learning_rate: float, controls=()) -> dict:
    """The readings of `correct`. ``reference`` is the family's plain
    reference (its ``mean_loss_and_grads``), ``params1`` are the seeded
    weights on one device, ``tokens``/``targets`` the whole first batch there. With
    ``controls`` also what the control reads: the reference computed in each
    of those precisions, its moments kept in the parameters' type, put in the
    program's place."""
    from benchmarks.reference import adamw as plain

    leaves = tuple(picks)
    hyper = dict(adamw, learning_rate=learning_rate)

    def plain_step(precision, as_program):
        loss, grads = reference.mean_loss_and_grads(params1, tokens, targets, precision=precision, leaves=leaves)
        out = {}
        for k in leaves:
            g = np.asarray(grads[k]).reshape(-1)[picks[k]]
            dt = sampled["dtype"][k]
            out[k] = plain.first_step(sampled["before"][k], g, param_dtype=dt,
                                      moment_dtype=dt if as_program else None, **hyper)
        return loss, out

    def readings(loss, mu, nu, after, ref_loss, want):
        r = {"step_loss_rel_err": abs(loss - ref_loss) / ref_loss}
        for k in leaves:
            w_mu, w_nu, w_after = want[k]
            r[f"step_mu_rel_err.{k}"] = rel(mu[k], w_mu)
            r[f"step_nu_rel_err.{k}"] = rel(nu[k], w_nu)
            moved = w_after - sampled["before"][k]
            if np.any(moved):  # a parameter type too coarse for the update moves nothing
                r[f"step_update_rel_err.{k}"] = rel(after[k] - sampled["before"][k], moved)
        return r

    ref_loss, want = plain_step("f32", as_program=False)
    verdict = {"step_loss": step_loss, "reference_loss": ref_loss,
               **readings(step_loss, sampled["mu"], sampled["nu"], sampled["after"], ref_loss, want)}
    for prec in controls:
        c_loss, c = plain_step(prec, as_program=True)
        verdict[f"control_{prec}"] = readings(
            c_loss, {k: c[k][0] for k in leaves}, {k: c[k][1] for k in leaves},
            {k: c[k][2] for k in leaves}, ref_loss, want)
    return verdict
