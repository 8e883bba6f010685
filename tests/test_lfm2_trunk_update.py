"""The fused update of the gated short-convolution / grouped-query /
sigmoid-routed SwiGLU-expert trunk (CONFIGS row 23, tiny preset, CPU)
against its plain float32 reference (tests/reference/lfm2_moe.py): loss,
gradients, priorities, routing counters, each short-convolution, attention
and expert block's output; the check's power to tell a wrong term or a
trunk in bfloat16; the step metrics; the scopes in the lowered step; a
short run through the learner's own loop with a checkpoint and a resume.
The layers themselves: tests/test_lfm2_trunk.py."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import lfm2_moe as family
from pytorch_distributed_tpu.models import hybrid
from pytorch_distributed_tpu.utils import profiling
from reference import lfm2_moe as reference
import test_hybrid
from test_lfm2_trunk import HYPER, REPO, TINY, model_hyper


def tiny_learner(tmp_path):
    return test_hybrid.tiny_learner(tmp_path, row=23,
                                    hybrid_preset="tiny-lfm2")


def fused_update(tmp_path):
    """test_hybrid's one K=1 fused update on a seeded ring, for row 23."""
    return test_hybrid.fused_update(tmp_path, row=23,
                                    hybrid_preset="tiny-lfm2")


def agreement(run, hyper):
    """The comparisons of benchmark/families/lfm2_moe.py ``agrees``, the
    blocks' inputs and outputs from that family's own ``walk``."""
    state = run["state"]
    loss, signal, grads, rows = reference.update_rows(
        state.params, state.target_params, run["batch"], hyper, 255.0)
    frames = run["batch"]["obs"][:, HYPER["pack_frames"] - 1:]
    pattern = run["model"].preset.pattern
    blocks = family.walk(run["model"], state.params, frames)
    rel = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    tree = state.params["params"]
    out_rel = {name: max(rel(o, reference.block_outputs(
        tree[f"layers_{i}"], u, kind, hyper["model"]))
        for i, (u, o, *_) in blocks.items() if pattern[i] == kind)
        for kind, name in family.COMPARED.items()}
    out_rel["route_weight"] = max(rel(w, reference.chosen_weights(
        tree[f"layers_{i}"], u, chosen, hyper["model"]))
        for i, (u, _, chosen, w) in ((i, b) for i, b in blocks.items()
                                     if len(b) == 4))
    leaves = lambda t: [np.asarray(x, np.float64).ravel()
                        for x in jax.tree_util.tree_leaves(t)]
    g, r = np.concatenate(leaves(run["grads"])), np.concatenate(leaves(grads))
    heavy = [(a, b) for a, b in zip(leaves(run["grads"]), leaves(grads))
             if np.vdot(b, b) > 1e-6 * np.vdot(r, r)]
    trunk = lambda t: leaves(family.without_head(t))
    norms = [(a, b) for a, b in zip(trunk(run["grads"]), trunk(grads))
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
                                      - 1.0) for a, b in norms),
        **{f"{name}_rel": v for name, v in out_rel.items()},
        "td_p50_over_mean": float(np.median(np.abs(got - signal))
                                  / np.mean(np.abs(signal))),
        "moe_rows_rel": float(np.max(np.abs(here - here_ref)
                                     / np.maximum(here_ref, 1.0)))}


def shipped_tolerance():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "lfm2_moe_pong.json")) as f:
        return json.load(f)["tolerance"]


def within(got, tol):
    higher = ("grad_cosine", "grad_cosine_leaf")
    return all(got[k] >= tol[k] if k in higher else got[k] <= tol[k]
               for k in got)


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    return fused_update(tmp_path_factory.mktemp("lfm2"))


def test_fused_update_is_the_reference_update(run):
    got = agreement(run, dict(HYPER, model=model_hyper()))
    # float32 on both sides: what is left is the order of summation
    assert got["loss_rel"] < 1e-4 and got["td_p50_over_mean"] < 1e-3, got
    assert got["grad_cosine"] > 0.9999 and got["grad_cosine_leaf"] > 0.999
    assert got["moe_rows_rel"] == 0.0
    assert max(got["sconv_out_rel"], got["attn_out_rel"],
               got["moe_out_rel"], got["route_weight_rel"]) < 1e-4, got
    assert got["grad_norm_leaf_rel"] < 1e-2
    assert within(got, shipped_tolerance())
    target = run["state"].target_params["params"]
    assert target["layers_0"]["w_in"].dtype == jnp.bfloat16
    assert target["layers_0"]["conv_w"].dtype == jnp.float32
    assert target["layers_2"]["q_norm"].dtype == jnp.float32
    assert target["layers_3"]["router"].dtype == jnp.float32
    assert not any("shared" in name for name in target["layers_3"])


def test_the_step_reports_the_routing_counters(run):
    m = run["metrics"]
    pairs = 4 * 16 * TINY.top_k
    assert float(m["learner/moe_rows_absent_share"]) == pytest.approx(
        1.0 - float(m["learner/moe_rows_here"]) / pairs)
    runs = hybrid.expert_runs(TINY, pairs)
    assert float(m["learner/moe_rows_here"]) <= float(
        m["learner/moe_rows_computed"]) <= sum(runs)
    assert float(m["learner/moe_load_max_over_mean"]) >= 1.0
    assert not {"learner/moe_aux_loss", "learner/gdn_decay_mean",
                "learner/kda_decay_mean"} & set(m)
    assert all(jnp.ndim(v) == 0 for v in m.values())   # loads are no metric


def test_the_selection_bias_steps_against_the_load(run):
    """Row 20's rule under the fourth trunk: after the optimizer ``b_sel``
    moves by ``bias_rate`` against each expert's load, and Adam never moves
    it."""
    before = run["state"].params["params"]["layers_3"]["b_sel"]
    after = run["state1"].params["params"]["layers_3"]["b_sel"]
    step = np.asarray(after - before)
    assert np.allclose(np.abs(step[step != 0]), TINY.bias_rate, rtol=1e-4)
    assert (step != 0).sum() >= TINY.n_experts - 1


@pytest.mark.parametrize("control", list(reference.WRONG) + ["bf16_trunk"])
def test_a_control_falls_outside_the_shipped_tolerances(run, control):
    """Each term gotten wrong, and the whole trunk in bfloat16, fails a
    shipped limit; a term of one block kind fails that block's own."""
    model = model_hyper(**family.CONTROLS[control])
    got = agreement(run, dict(HYPER, model=model))
    tol = shipped_tolerance()
    assert not within(got, tol), got
    tells = {"no_conv_gate": "sconv_out_rel", "conv_shift": "sconv_out_rel",
             "no_qk_norm": "attn_out_rel", "half_rotary": "attn_out_rel",
             "b_sel_in_weights": "route_weight_rel",
             "no_topk_renorm": "route_weight_rel", "bf16_trunk":
             "route_weight_rel"}
    if control in tells:
        assert got[tells[control]] > tol[tells[control]], got


def test_the_models_parts_are_named_inside_checkpoint_and_scan(tmp_path):
    """The new scopes stand in the fused step's lowered program, on the
    forward's path, the target's and the backward's, the conv's middle under
    ``sconv.mix`` inside ``model.sconv``; no shared expert's scope, no other
    trunk's mixer."""
    opt, spec, model, state, step, replay = tiny_learner(tmp_path)
    fused = replay.build_fused_step(step, 4, donate=False, steps_per_call=1)
    text = fused.lower(state, replay.state, jax.random.PRNGKey(0),
                       jnp.float32(0.6)).as_text(debug_info=True)
    lines = [ln for ln in text.splitlines() if "loc(" in ln]
    for scope in (profiling.SCOPE_SCONV, profiling.SCOPE_SCONV_MIX,
                  profiling.SCOPE_ATTN, profiling.SCOPE_MLP,
                  profiling.SCOPE_MOE):
        assert any(scope in ln and "transpose(" in ln for ln in lines), scope
        assert any(scope in ln and profiling.PHASE_TARGET in ln
                   for ln in lines), scope
        assert any(scope in ln and "checkpoint" in ln for ln in lines), scope
    mix = [ln for ln in lines if profiling.SCOPE_SCONV_MIX in ln]
    assert mix and all(profiling.SCOPE_SCONV in ln for ln in mix)
    for other in (profiling.SCOPE_SSM, profiling.SCOPE_GDN,
                  profiling.SCOPE_KDA, profiling.SCOPE_MLA,
                  profiling.SCOPE_MOE_SHARED):
        assert other not in text


@pytest.mark.timeout(600)
def test_row_23_trains_acts_checkpoints_and_resumes(tmp_path):
    """The normal path end to end at the tiny preset: an actor acting
    through the carry (conv tails, rotated key ring), the learner's fused
    K = 1 step, ``scalars.jsonl`` with the row's counters, a checkpoint, and
    a second run that resumes from it."""
    from pytorch_distributed_tpu import runtime
    from pytorch_distributed_tpu.config import build_options

    common = dict(
        root_dir=str(tmp_path), refs="lfm23", hybrid_preset="tiny-lfm2",
        num_actors=1, num_envs_per_actor=4, memory_size=4096, batch_size=4,
        seq_len=31, seq_overlap=15, burn_in=8, nstep=3, learn_start=8,
        target_model_update=10, max_replay_ratio=64.0, learner_freq=5,
        evaluator_nepisodes=0, visualize=False)
    runtime.train(build_options(23, steps=10, **common), backend="thread")
    rows = [json.loads(ln) for ln in open(os.path.join(
        str(tmp_path), "logs", "lfm23", "scalars.jsonl"))]
    tags = {r["tag"] for r in rows}
    assert {"learner/critic_loss", "learner/moe_rows_here",
            "learner/moe_rows_computed",
            "learner/moe_load_max_over_mean"} <= tags, sorted(tags)
    first = max(r["step"] for r in rows if r["tag"] == "learner/critic_loss")
    runtime.train(build_options(23, steps=20, **common), backend="thread")
    rows = [json.loads(ln) for ln in open(os.path.join(
        str(tmp_path), "logs", "lfm23", "scalars.jsonl"))]
    assert max(r["step"] for r in rows
               if r["tag"] == "learner/critic_loss") > first >= 5
