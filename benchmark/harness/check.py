"""The comparison that decides ``correct``: one fused update of the program,
at the cell's real widths and on the ring it just ran on, against the
configuration's plain float32 reference.  Runs AFTER the measured window, so
its one-off programs (a K=1 fused step, the reference) cost neither the
window nor ``setup_s``.

What is compared, and how tightly (the tolerances are the configuration's
``tolerance`` group, with their reason beside them in the file):

  sampler     every drawn index lies inside the float64 CDF bracket of its
              uniform (as tools/kernel_check.py checks the Pallas sampler),
              holds a positive priority and is below the fill
  loss        relative error of the step's loss
  td          per-row priority signal recovered from the priorities the
              step wrote back, against the reference's, relative to its
              mean: the 90th percentile of the rows' errors
  priorities  the rows the step rewrote are exactly the rows it drew
  gradient    cosine between the program's gradient, recovered from the
              change of Adam's first moment, and the reference's, in float64
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

ADAM_B1 = 0.9     # optax.adam's default, which ops/losses.make_optimizer uses


def _first_moment(opt_state) -> Any:
    """Adam's ``mu`` tree inside the program's optax chain."""
    import optax

    return optax.tree_utils.tree_get(opt_state, "mu")


def _flat64(tree) -> np.ndarray:
    import jax

    return np.concatenate([np.asarray(x, np.float64).ravel()
                           for x in jax.tree_util.tree_leaves(tree)])


def cdf_brackets(priority: np.ndarray, index: np.ndarray, u: np.ndarray,
                 fill: int) -> Dict[str, Any]:
    """Each draw ``i`` aimed at ``u[i] * total`` of the cumulative priority
    mass; its index must bracket that target in a float64 CDF, within a few
    float32 roundings of the total (summation order differs between
    samplers)."""
    cdf = np.cumsum(priority.astype(np.float64))
    total = cdf[-1]
    tol = 8.0 * float(np.spacing(np.float32(total)))
    target = u.astype(np.float64) * total
    lo = np.where(index > 0, cdf[np.maximum(index - 1, 0)], 0.0)
    inside = (lo - tol <= target) & (target <= cdf[index] + tol)
    valid = (priority[index] > 0) & (index < fill)
    return {"draws": int(index.size), "outside": int((~inside).sum()),
            "invalid": int((~valid).sum())}


def fused_update_agrees(lrn, cfg: Dict[str, Any], reference,
                        seed: int) -> Dict[str, Any]:
    """``lrn`` is a ``program.Learner`` whose ring holds rows.  Consumes its
    train state and frees its ring."""
    import jax
    import jax.numpy as jnp

    from . import program

    replay, opt = lrn.replay, lrn.opt
    tol = cfg["tolerance"]
    B = opt.agent_params.batch_size
    key = jax.random.PRNGKey(seed ^ 0x5EED)
    beta = np.float32(replay.beta(0))

    # the batch the fused step will draw: same key, same priorities, through
    # the program's own sampling function
    sample = replay.sample(B, key, beta=beta)
    index = np.asarray(sample.index)
    batch = jax.device_get(reference.batch_of(sample))
    pri0 = np.asarray(replay.state.priority)
    fill = int(replay.state.fill)
    u = np.asarray(jax.random.uniform(key, (B,)))
    out: Dict[str, Any] = {"sampler": cdf_brackets(pri0, index, u, fill)}

    before = jax.device_get(lrn.state)
    mu0 = _flat64(_first_moment(before.opt_state))
    fused1 = program.build_fused(lrn, steps_per_call=1)
    state1, ring1, metrics = fused1(lrn.state, replay.state, key,
                                    jax.device_put(beta))
    metrics = jax.device_get(metrics)
    mu1 = _flat64(_first_moment(jax.device_get(state1.opt_state)))
    pri1 = np.asarray(ring1.priority)
    # free the ring before the float32 reference needs the memory
    lrn.state = replay.state = None
    del state1, ring1, sample

    grad = (mu1 - ADAM_B1 * mu0) / (1.0 - ADAM_B1)
    loss_ref, signal_ref, grads_ref = reference.update(
        before.params, before.target_params,
        {k: jnp.asarray(v) for k, v in batch.items()},
        cfg["reference_hyper"], lrn.spec.norm_val)
    loss_ref = float(loss_ref)
    signal_ref = np.asarray(signal_ref, np.float64)
    gref = _flat64(grads_ref)

    loss = float(metrics["learner/critic_loss"])
    out["loss"] = {"program": loss, "reference": loss_ref,
                   "rel_err": abs(loss - loss_ref) / max(abs(loss_ref), 1e-12)}
    out["grad_cosine"] = float(
        grad @ gref / max(np.linalg.norm(grad) * np.linalg.norm(gref), 1e-30))
    out["grad_norm"] = {"program": float(np.linalg.norm(grad)),
                        "reference": float(np.linalg.norm(gref))}

    # rows drawn once: their new priority is (signal + eps)^alpha
    rows, counts = np.unique(index, return_counts=True)
    once = np.isin(index, rows[counts == 1])
    signal = pri1[index[once]].astype(np.float64) ** (1.0 / replay.alpha) \
        - reference.PRIORITY_EPS
    scale = max(float(np.mean(np.abs(signal_ref))), 1e-12)
    err = np.sort(np.abs(signal - signal_ref[once])) / scale
    # judged on the 90th percentile (nearest rank): where two Q-values are
    # nearly equal, bf16 and float32 may pick different bootstrap actions,
    # and that row's signal then differs by a whole TD error (seen on the
    # chip, PR 22); a wrong term moves every row
    out["td"] = {"rows": int(err.size),
                 "p90_err_over_mean": float(
                     err[max(0, int(np.ceil(0.9 * err.size)) - 1)]),
                 "max_err_over_mean": float(err[-1])}
    changed = np.flatnonzero(pri0 != pri1)
    out["priorities"] = {"rewritten": int(changed.size),
                         "drawn": int(rows.size),
                         "not_drawn": int((~np.isin(changed, rows)).sum())}
    out["skipped"] = float(metrics.get("learner/skipped", 0.0))

    out["ok"] = bool(
        out["sampler"]["outside"] == 0 and out["sampler"]["invalid"] == 0
        and out["loss"]["rel_err"] <= tol["loss_rel"]
        and out["grad_cosine"] >= tol["grad_cosine"]
        and out["td"]["p90_err_over_mean"] <= tol["td_p90_over_mean"]
        and out["priorities"]["not_drawn"] == 0
        and out["priorities"]["rewritten"] >= rows.size - tol.get(
            "unchanged_rows", 2)
        and out["skipped"] == 0.0)
    return out
