"""The fused update of the channel-gated delta-rule / latent-attention /
sigmoid-routed SwiGLU-expert trunk (CONFIGS row 22, tiny preset, CPU)
against its plain float32 reference (tests/reference/kimi_linear.py): loss,
gradients, priorities, routing counters, the delta rule's last states and
the latent attention's output; the check's power to tell a wrong term; the
step metrics; the scopes in the lowered step; a short run through the
learner's own loop with a checkpoint and a resume.  The layers themselves:
tests/test_kimi_trunk.py."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.models import hybrid
from pytorch_distributed_tpu.utils import profiling
from reference import kimi_linear as reference
import test_hybrid
from test_kimi_trunk import HYPER, REPO, TINY, model_hyper


def tiny_learner(tmp_path):
    return test_hybrid.tiny_learner(tmp_path, row=22,
                                    hybrid_preset="tiny-kimi")


def fused_update(tmp_path):
    """test_hybrid's one K=1 fused update on a seeded ring, for row 22."""
    return test_hybrid.fused_update(tmp_path, row=22,
                                    hybrid_preset="tiny-kimi")


def agreement(run, hyper):
    """The comparisons of benchmark/families/kimi_linear.py ``agrees`` (its
    second look at the delta rule with a slowed decay is rehearsed in
    benchmark/tests: it tells a bfloat16 state, which float32 on the CPU
    over 16 positions does not have)."""
    state = run["state"]
    loss, signal, grads, rows = reference.update_rows(
        state.params, state.target_params, run["batch"], hyper, 255.0)
    frames = run["batch"]["obs"][:, HYPER["pack_frames"] - 1:]
    states = run["model"].apply(state.params, frames,
                                method=run["model"].window_pass)[2]
    states_ref = reference.window_states(state.params, frames,
                                         hyper["model"], 255.0)[1]
    rel = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    # the latent attention's output on the program's own input to it
    c, tree = run["model"].preset, state.params["params"]
    at = c.pattern.index("L")
    x = run["model"].apply(state.params, frames,
                           method=lambda m, f: m._embed(f))
    for i, kind in enumerate(c.pattern[:at]):
        p = tree[f"layers_{i}"]
        u = hybrid.rms_norm(x, p["norm"], c.norm_eps)
        x = x + (hybrid.kda_window(p, u, c, jnp.float32)[0] if kind == "K"
                 else hybrid.mlp_block(p, u, jnp.float32))
    p = tree[f"layers_{at}"]
    u = hybrid.rms_norm(x, p["norm"], c.norm_eps)
    attended = hybrid.mla_window(p, u, c, jnp.float32)
    attended_ref = reference.latent_outputs(p, u, hyper["model"])
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
    return {
        "loss_rel": abs(float(run["metrics"]["learner/critic_loss"])
                        - float(loss)) / abs(float(loss)),
        "grad_cosine": float(g @ r / (np.linalg.norm(g)
                                      * np.linalg.norm(r))),
        "grad_cosine_leaf": min(float(np.vdot(a, b) / max(
            np.linalg.norm(a) * np.linalg.norm(b), 1e-300))
            for a, b in heavy),
        "grad_norm_leaf_rel": max(abs(np.linalg.norm(a) / np.linalg.norm(b)
                                      - 1.0) for a, b in heavy),
        "kda_state_rel": max(rel(a, b) for a, b in zip(states.values(),
                                                       states_ref)),
        "mla_out_rel": rel(attended, attended_ref),
        "td_p50_over_mean": float(np.median(np.abs(got - signal))
                                  / np.mean(np.abs(signal))),
        "moe_rows_rel": float(np.max(np.abs(here - here_ref)
                                     / np.maximum(here_ref, 1.0)))}


def shipped_tolerance():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "kimi_linear_pong.json")) as f:
        return json.load(f)["tolerance"]


def within(got, tol):
    return (got["loss_rel"] <= tol["loss_rel"]
            and got["grad_cosine"] >= tol["grad_cosine"]
            and got["grad_cosine_leaf"] >= tol["grad_cosine_leaf"]
            and got["grad_norm_leaf_rel"] <= tol["grad_norm_leaf_rel"]
            and got["td_p50_over_mean"] <= tol["td_p50_over_mean"]
            and got["moe_rows_rel"] <= tol["moe_rows_rel"]
            and got["kda_state_rel"] <= tol["kda_state_rel"]
            and got["mla_out_rel"] <= tol["mla_out_rel"])


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    return fused_update(tmp_path_factory.mktemp("kimi"))


def test_fused_update_is_the_reference_update(run):
    got = agreement(run, dict(HYPER, model=model_hyper()))
    assert got["loss_rel"] < 1e-4 and got["td_p50_over_mean"] < 1e-3, got
    assert got["grad_cosine"] > 0.9999 and got["grad_cosine_leaf"] > 0.999
    assert got["moe_rows_rel"] == 0.0
    assert got["kda_state_rel"] < 1e-4 and got["mla_out_rel"] < 1e-4
    assert got["grad_norm_leaf_rel"] < 1e-2
    assert within(got, shipped_tolerance())
    target = run["state"].target_params["params"]
    assert target["layers_0"]["w_fb"].dtype == jnp.bfloat16
    assert target["layers_0"]["A_log"].dtype == jnp.float32
    assert target["layers_0"]["dt_bias"].dtype == jnp.float32
    assert target["layers_2"]["kv_norm"].dtype == jnp.float32
    assert target["layers_3"]["router"].dtype == jnp.float32


def test_the_step_reports_the_decay_and_the_routing_counters(run):
    m = run["metrics"]
    # the mean over every channel, and the channel that forgets fastest
    assert 0.0 < float(m["learner/kda_decay_min"]) \
        < float(m["learner/kda_decay_mean"]) < 1.0
    pairs = 4 * 16 * TINY.top_k
    assert float(m["learner/moe_rows_absent_share"]) == pytest.approx(
        1.0 - float(m["learner/moe_rows_here"]) / pairs)
    runs = hybrid.expert_runs(TINY, pairs)
    assert float(m["learner/moe_rows_here"]) <= float(
        m["learner/moe_rows_computed"]) <= sum(runs)
    assert float(m["learner/moe_load_max_over_mean"]) >= 1.0
    assert "learner/moe_aux_loss" not in m and "learner/gdn_decay_mean" \
        not in m
    assert all(jnp.ndim(v) == 0 for v in m.values())   # loads are no metric


def test_the_selection_bias_steps_against_the_load(run):
    """Row 20's rule under the third trunk: after the optimizer ``b_sel``
    moves by ``bias_rate`` against each expert's load, and Adam never moves
    it."""
    before = run["state"].params["params"]["layers_3"]["b_sel"]
    after = run["state1"].params["params"]["layers_3"]["b_sel"]
    step = np.asarray(after - before)
    assert np.allclose(np.abs(step[step != 0]), TINY.bias_rate, rtol=1e-4)
    assert (step != 0).sum() >= TINY.n_experts - 1


@pytest.mark.parametrize("wrong", reference.WRONG)
def test_a_wrong_term_falls_outside_the_shipped_tolerances(run, wrong):
    got = agreement(run, dict(HYPER, model=model_hyper(wrong=(wrong,))))
    assert not within(got, shipped_tolerance()), got
    if wrong == "head_mean_decay":
        # the scalar-gated rule of row 21 under these weights: told by the
        # state itself, not only downstream
        assert got["kda_state_rel"] > shipped_tolerance()["kda_state_rel"]


def test_the_models_parts_are_named_inside_checkpoint_and_scan(tmp_path):
    """The new scopes stand in the fused step's lowered program, on the
    forward's path, the target's and the backward's, the recurrence proper
    under ``kda.chunk`` inside ``model.kda``."""
    opt, spec, model, state, step, replay = tiny_learner(tmp_path)
    fused = replay.build_fused_step(step, 4, donate=False, steps_per_call=1)
    text = fused.lower(state, replay.state, jax.random.PRNGKey(0),
                       jnp.float32(0.6)).as_text(debug_info=True)
    lines = [ln for ln in text.splitlines() if "loc(" in ln]
    for scope in (profiling.SCOPE_KDA, profiling.SCOPE_KDA_CHUNK,
                  profiling.SCOPE_MLA, profiling.SCOPE_MLP,
                  profiling.SCOPE_MOE):
        assert any(scope in ln and "transpose(" in ln for ln in lines), scope
        assert any(scope in ln and profiling.PHASE_TARGET in ln
                   for ln in lines), scope
        assert any(scope in ln and "checkpoint" in ln for ln in lines), scope
    chunk = [ln for ln in lines if profiling.SCOPE_KDA_CHUNK in ln]
    assert chunk and all(profiling.SCOPE_KDA in ln for ln in chunk)
    assert any("while" in ln for ln in chunk)      # the scan over chunks
    for other in (profiling.SCOPE_GDN, profiling.SCOPE_SSM,
                  profiling.SCOPE_ATTN):
        assert other not in text


@pytest.mark.timeout(600)
def test_row_22_trains_acts_checkpoints_and_resumes(tmp_path):
    """The normal path end to end at the tiny preset: an actor acting
    through the carry (latent ring, delta-rule state), the learner's fused
    K = 1 step, ``scalars.jsonl`` with the row's counters, a checkpoint, and
    a second run that resumes from it."""
    from pytorch_distributed_tpu import runtime
    from pytorch_distributed_tpu.config import build_options

    common = dict(
        root_dir=str(tmp_path), refs="kimi22", hybrid_preset="tiny-kimi",
        num_actors=1, num_envs_per_actor=4, memory_size=4096, batch_size=4,
        seq_len=31, seq_overlap=15, burn_in=8, nstep=3, learn_start=8,
        target_model_update=10, max_replay_ratio=64.0, learner_freq=5,
        evaluator_nepisodes=0, visualize=False)
    runtime.train(build_options(22, steps=10, **common), backend="thread")
    rows = [json.loads(ln) for ln in open(os.path.join(
        str(tmp_path), "logs", "kimi22", "scalars.jsonl"))]
    tags = {r["tag"] for r in rows}
    assert {"learner/critic_loss", "learner/kda_decay_mean",
            "learner/kda_decay_min", "learner/moe_rows_here",
            "learner/moe_rows_computed",
            "learner/moe_load_max_over_mean"} <= tags, sorted(tags)
    decay = [r["value"] for r in rows if r["tag"] == "learner/kda_decay_min"]
    assert all(0.0 < v < 1.0 for v in decay)
    first = max(r["step"] for r in rows if r["tag"] == "learner/critic_loss")
    runtime.train(build_options(22, steps=20, **common), backend="thread")
    rows = [json.loads(ln) for ln in open(os.path.join(
        str(tmp_path), "logs", "kimi22", "scalars.jsonl"))]
    assert max(r["step"] for r in rows
               if r["tag"] == "learner/critic_loss") > first >= 5
