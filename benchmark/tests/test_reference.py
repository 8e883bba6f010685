"""The plain references against the program at a small size, in float32, and
the check's power to tell: a reference with one term of the mathematics
changed must fall outside the tolerances the configurations ship with."""

import json
import os
import types

import numpy as np
import pytest

from benchmark.harness import check, manifest, program


def small_learner(tmp_path, family: str, row: int, **overrides):
    cfg = {"row": row, "overrides": dict(overrides, compute_dtype="float32")}
    opt = program.build_opt(cfg, seed=3, run_dir=str(tmp_path / "run"),
                            refs="t", num_actors=0, evaluator_nepisodes=0)
    lrn = program.build_learner(opt)
    program.fill_ring(lrn, seed=3, chunk_rows=32,
                      family=manifest.load_module("families", family))
    return lrn


def shipped_tolerance(name: str) -> dict:
    with open(os.path.join(manifest.BENCH_DIR, "configs",
                           f"{name}.json")) as f:
        return json.load(f)["tolerance"]


DQN = dict(row=12, memory_size=128, batch_size=16)
R2D2 = dict(row=14, memory_size=128, batch_size=4, seq_len=8, seq_overlap=4,
            burn_in=2, nstep=2)
R2D2_HYPER = {"burn_in": 2, "nstep": 2, "gamma": 0.99, "eta": 0.9,
              "double": True, "value_rescale": True, "pack_frames": 4}


def without(reference, **changed):
    """The reference with fields of the batch overwritten: a term of the
    mathematics dropped (importance weights, the terminal mask)."""
    def update(params, target, batch, hyper, norm_val):
        batch = dict(batch, **{k: f(batch[k]) for k, f in changed.items()})
        return reference.update(params, target, batch, hyper, norm_val)

    return types.SimpleNamespace(update=update, batch_of=reference.batch_of,
                                 PRIORITY_EPS=reference.PRIORITY_EPS)


@pytest.mark.parametrize("family, build, config, hyper, wrong", [
    ("dqn", DQN, "apex_pong", {"double": False},
     dict(weight=np.ones_like)),
    ("dqn", DQN, "apex_pong", {"double": False},
     dict(terminal1=np.zeros_like)),
    ("r2d2", R2D2, "r2d2_pong", R2D2_HYPER,
     dict(R2D2_HYPER, value_rescale=False)),
    ("r2d2", R2D2, "r2d2_pong", R2D2_HYPER, dict(R2D2_HYPER, burn_in=0)),
    ("r2d2", R2D2, "r2d2_pong", R2D2_HYPER, dict(R2D2_HYPER, nstep=1)),
], ids=["dqn-weights", "dqn-terminal", "r2d2-rescale", "r2d2-burn-in",
        "r2d2-nstep"])
def test_reference_agrees_and_a_changed_term_does_not(
        tmp_path, family, build, config, hyper, wrong):
    reference = manifest.load_module("reference", family)
    tol = shipped_tolerance(config)
    cfg = {"tolerance": tol, "reference_hyper": hyper}
    got = check.fused_update_agrees(small_learner(tmp_path, family, **build),
                                    cfg, reference, seed=3)
    assert got["ok"], got
    # float32 against float32: far inside what bf16 is allowed
    assert got["loss"]["rel_err"] < 1e-4 and got["grad_cosine"] > 0.9999
    assert got["td"]["max_err_over_mean"] < 1e-3

    if family == "dqn":
        wrong_reference, wrong_hyper = without(reference, **wrong), hyper
    else:
        wrong_reference, wrong_hyper = reference, wrong
    bad = check.fused_update_agrees(
        small_learner(tmp_path, family, **build),
        {"tolerance": tol, "reference_hyper": wrong_hyper}, wrong_reference,
        seed=3)
    assert not bad["ok"], bad


def test_cdf_brackets_catch_a_wrong_index():
    p = np.array([1.0, 0.0, 2.0, 1.0], np.float32)      # cdf 1, 1, 3, 4
    u = np.array([0.1, 0.3, 0.7, 0.9], np.float32)      # targets .4 1.2 2.8 3.6
    good = np.array([0, 2, 2, 3])
    assert check.cdf_brackets(p, good, u, fill=4) == {
        "draws": 4, "outside": 0, "invalid": 0}
    off = check.cdf_brackets(p, np.array([0, 1, 2, 2]), u, fill=4)
    assert off["outside"] == 2 and off["invalid"] == 1   # row 1 is empty
