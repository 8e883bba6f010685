"""The fused update of the gated-delta-rule / softmax-routed gated-expert /
gated-attention trunk (CONFIGS row 21, tiny preset, CPU) against its plain
float32 reference (tests/reference/qwen3_next.py): loss, balance loss,
gradients, priorities, routing counters and the delta rule's last states;
the check's power to tell a wrong term; the scopes in the lowered step.  The
layers themselves: tests/test_gated_delta.py."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.models import hybrid
from pytorch_distributed_tpu.ops.sequence_losses import AUX_LOSS_KEY
from pytorch_distributed_tpu.utils import profiling
from reference import qwen3_next as reference
import test_hybrid
from test_gated_delta import HYPER, REPO, TINY, model_hyper


# -- (c) the fused update -------------------------------------------------------------

def tiny_learner(tmp_path):
    return test_hybrid.tiny_learner(tmp_path, row=21,
                                    hybrid_preset="tiny-qwen")


def fused_update(tmp_path):
    """test_hybrid's one K=1 fused update on a seeded ring, for row 21."""
    return test_hybrid.fused_update(tmp_path, row=21,
                                    hybrid_preset="tiny-qwen")


def agreement(run, hyper):
    """The comparisons of benchmark/families/qwen3_next.py ``agrees`` (its
    second look at the delta rule with a slowed decay is rehearsed in
    benchmark/tests: it tells a bfloat16 state, which float32 on the CPU
    over 16 positions does not have)."""
    state = run["state"]
    loss, signal, grads, rows, balance = reference.update_rows(
        state.params, state.target_params, run["batch"], hyper, 255.0)
    frames = run["batch"]["obs"][:, HYPER["pack_frames"] - 1:]
    states = run["model"].apply(state.params, frames,
                                method=run["model"].window_pass)[2]
    states_ref = reference.window_states(state.params, frames,
                                         hyper["model"], 255.0)[1]
    rel = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    # the attention block's output on the program's own input to it
    c, tree = run["model"].preset, state.params["params"]
    at = c.pattern.index("*")
    x = run["model"].apply(state.params, frames,
                           method=lambda m, f: m._embed(f))
    for i, kind in enumerate(c.pattern[:at]):
        p = tree[f"layers_{i}"]
        u = hybrid.rms_norm(x, hybrid.norm_scale(p["norm"], c), c.norm_eps)
        out = hybrid.gdn_window(p, u, c, jnp.float32)[0] if kind == "D" \
            else hybrid.moe_apply(p, u.reshape(-1, c.d_model), c,
                                  jnp.float32)[0].reshape(u.shape)
        x = x + out
    p = tree[f"layers_{at}"]
    u = hybrid.rms_norm(x, hybrid.norm_scale(p["norm"], c), c.norm_eps)
    attended = hybrid.attention_window(p, u, c, jnp.float32)
    attended_ref = reference.attention_outputs(p, u, hyper["model"])
    leaves = lambda t: [np.asarray(x, np.float64).ravel()
                        for x in jax.tree_util.tree_leaves(t)]
    g, r = np.concatenate(leaves(run["grads"])), np.concatenate(leaves(grads))
    heavy = [(a, b) for a, b in zip(leaves(run["grads"]), leaves(grads))
             if np.vdot(b, b) > 1e-6 * np.vdot(r, r)]
    index = np.asarray(run["sample"].index)
    got = np.asarray(run["ring"].priority)[index].astype(np.float64) ** (
        1.0 / run["replay"].alpha) - reference.PRIORITY_EPS
    signal = np.asarray(signal, np.float64)
    here = np.array([float(v) for k, v in sorted(run["metrics"].items())
                     if k.startswith("learner/moe_rows_here/")])
    here_ref = np.asarray(rows, np.float64).sum(axis=0)
    aux = float(run["metrics"][AUX_LOSS_KEY])
    return {
        "loss_rel": abs(float(run["metrics"]["learner/critic_loss"])
                        - float(loss)) / abs(float(loss)),
        "aux_rel": abs(aux - float(balance)) / max(aux, 1e-12),
        "grad_cosine": float(g @ r / (np.linalg.norm(g)
                                      * np.linalg.norm(r))),
        "grad_cosine_leaf": min(float(np.vdot(a, b) / max(
            np.linalg.norm(a) * np.linalg.norm(b), 1e-300))
            for a, b in heavy),
        "grad_norm_leaf_rel": max(abs(np.linalg.norm(a) / np.linalg.norm(b)
                                      - 1.0) for a, b in heavy),
        "gdn_state_rel": max(rel(a, b) for a, b in zip(states.values(),
                                                       states_ref)),
        "attn_out_rel": rel(attended, attended_ref),
        "td_p50_over_mean": float(np.median(np.abs(got - signal))
                                  / np.mean(np.abs(signal))),
        "moe_rows_rel": float(np.max(np.abs(here - here_ref)
                                     / np.maximum(here_ref, 1.0)))}


def shipped_tolerance():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "qwen3_next_pong.json")) as f:
        return json.load(f)["tolerance"]


def within(got, tol):
    return (got["loss_rel"] <= tol["loss_rel"]
            and got["aux_rel"] <= tol["aux_rel"]
            and got["grad_cosine"] >= tol["grad_cosine"]
            and got["grad_cosine_leaf"] >= tol["grad_cosine_leaf"]
            and got["grad_norm_leaf_rel"] <= tol["grad_norm_leaf_rel"]
            and got["td_p50_over_mean"] <= tol["td_p50_over_mean"]
            and got["moe_rows_rel"] <= tol["moe_rows_rel"]
            and got["gdn_state_rel"] <= tol["gdn_state_rel"]
            and got["attn_out_rel"] <= tol["attn_out_rel"])


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    return fused_update(tmp_path_factory.mktemp("qwen"))


def test_fused_update_is_the_reference_update(run):
    got = agreement(run, dict(HYPER, model=model_hyper()))
    assert got["loss_rel"] < 1e-4 and got["td_p50_over_mean"] < 1e-3, got
    assert got["aux_rel"] < 1e-4, got
    assert got["grad_cosine"] > 0.9999 and got["grad_cosine_leaf"] > 0.999
    assert got["moe_rows_rel"] == 0.0
    assert got["gdn_state_rel"] < 1e-4 and got["grad_norm_leaf_rel"] < 1e-2
    assert within(got, shipped_tolerance())
    target = run["state"].target_params["params"]
    assert target["layers_0"]["w_qkvz"].dtype == jnp.bfloat16
    assert target["layers_0"]["A_log"].dtype == jnp.float32
    assert target["layers_1"]["router"].dtype == jnp.float32
    assert target["layers_1"]["shared_gate"].dtype == jnp.float32
    m = run["metrics"]
    pairs = 4 * 16 * TINY.top_k
    assert float(m["learner/moe_rows_absent_share"]) == pytest.approx(
        1.0 - float(m["learner/moe_rows_here"]) / pairs)
    # every run that holds a routed row is computed whole
    runs = hybrid.expert_runs(TINY, pairs)
    assert float(m["learner/moe_rows_here"]) <= float(
        m["learner/moe_rows_computed"]) <= sum(runs)
    assert float(m["learner/moe_rows_computed"]) % (runs[0] / 2) == 0
    assert 0.0 < float(m["learner/gdn_decay_mean"]) < 1.0
    # the balance loss: two expert layers, each >= 1, weighed by 1e-3
    assert float(m[AUX_LOSS_KEY]) >= 2 * TINY.aux_weight
    assert all(jnp.ndim(v) == 0 for v in m.values())   # loads are no metric


def test_the_balance_loss_reaches_the_router_and_nothing_steps_a_bias(run):
    """The gradient of the router holds the balance loss's part; the
    program has no ``b_sel`` and no ``after_update``."""
    assert "b_sel" not in run["state"].params["params"]["layers_1"]
    assert run["model"].train_parts(4)[2] is None
    hyper = dict(HYPER, model=model_hyper(wrong=("no_aux",)))
    got = agreement(run, hyper)
    assert got["aux_rel"] > 0.99 and not within(got, shipped_tolerance())


@pytest.mark.parametrize("wrong", [w for w in reference.WRONG
                                   if w != "no_aux"])
def test_a_wrong_term_falls_outside_the_shipped_tolerances(run, wrong):
    got = agreement(run, dict(HYPER, model=model_hyper(wrong=(wrong,))))
    assert not within(got, shipped_tolerance()), got


def test_the_models_parts_are_named_inside_checkpoint_and_scan(tmp_path):
    """The delta rule's scopes stand in the fused step's lowered program,
    on the forward's path and on the backward's, the recurrence proper
    under ``gdn.chunk`` inside ``model.gdn``."""
    opt, spec, model, state, step, replay = tiny_learner(tmp_path)
    fused = replay.build_fused_step(step, 4, donate=False, steps_per_call=1)
    text = fused.lower(state, replay.state, jax.random.PRNGKey(0),
                       jnp.float32(0.6)).as_text(debug_info=True)
    lines = [ln for ln in text.splitlines() if "loc(" in ln]
    for scope in (profiling.SCOPE_GDN, profiling.SCOPE_GDN_CHUNK,
                  profiling.SCOPE_ATTN, profiling.SCOPE_MOE):
        assert any(scope in ln and "transpose(" in ln for ln in lines), scope
        assert any(scope in ln and profiling.PHASE_TARGET in ln
                   for ln in lines), scope
    chunk = [ln for ln in lines if profiling.SCOPE_GDN_CHUNK in ln]
    assert chunk and all(profiling.SCOPE_GDN in ln for ln in chunk)
    assert any("while" in ln for ln in chunk)      # the scan over chunks
