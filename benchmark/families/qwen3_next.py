"""Family ``qwen3_next``: sequence Q-learning on segments drawn from the HBM
segment ring through the second hybrid trunk of ``models/hybrid.py``
(``PRESETS["qwen3-next-4"]``): gated-delta-rule linear-attention mixers, one
gated softmax-attention mixer with rotary and query / key norms, a
softmax-routed SwiGLU expert block after each, of which this chip holds some
of each block's experts, and a load-balancing loss beside the TD loss.  The
ring, its feed, sampler, write-back and the sparse seeded frames are the
``r2d2`` and ``nemotron_h`` families'."""

from __future__ import annotations

from typing import Any, Dict, Sequence

import numpy as np

from ..harness import check, program
from ..harness.shapes import dense_flops
from .nemotron_h import (build_step, gradient_agreement,  # noqa: F401
                         newest_frames, rel_err, seed_chunk,
                         update_priorities)

# the published names of the sizes the trunk is built from: the keys of the
# configuration's ``shapes`` group and of its file's top level alike
MODEL_KEYS = (
    "layer_pattern", "hidden_size", "linear_num_key_heads",
    "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim",
    "linear_conv_kernel_dim", "gdn_chunk", "num_attention_heads",
    "num_key_value_heads", "head_dim", "partial_rotary_factor", "rope_theta",
    "num_experts_published", "num_experts", "first_expert",
    "num_experts_per_tok", "moe_intermediate_size",
    "shared_expert_intermediate_size", "norm_topk_prob", "rms_norm_eps",
    "router_aux_loss_coef")


# added to a D block's ``dt_bias`` for the check's second look at its
# recurrence: at the seeded init (``dt_bias`` 1, ``A`` ~ U(0, 16), the
# family's) the state forgets within a position (mean ``exp(g)`` 0.058), a
# bfloat16 state is then one rounding (0.0016) from a float32 one, and the
# program's own 0.0044 from its bf16 operands hides it; at -8 the memory is a
# trained model's (mean ``exp(g)`` 0.99) and the state's precision shows
SLOW_DECAY_SHIFT = -8.0


def forward_flops(m: Dict[str, Any], positions: int, frame: int,
                  num_actions: int) -> Dict[str, float]:
    """FLOPs one position's forward pass needs HERE, by block kind (all
    blocks of the kind together), in a window of ``positions``: the experts
    held only, at their expected load; the causal half of attention; the
    delta rule in its chunked form AS THE ALGORITHM NEEDS IT: inside a chunk
    of L the causal half of ``K K^T`` and ``Q K^T`` a key head; a value
    head's triangular system solved against its keys and values (the causal
    half of an L x L product with d_k + d_v columns: the inverse the program
    forms by ten dense L x L products is its own affair and is not
    counted), the chunk's corrections ``W S``, its read-out ``Q S`` and
    ``(Q K^T) V'``, and its state update ``K^T V'``."""
    d = m["hidden_size"]
    hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv, L = (m["linear_key_head_dim"], m["linear_value_head_dim"],
                 m["gdn_chunk"])
    conv_dim = 2 * hk * dk + hv * dv
    gdn = (dense_flops(d, conv_dim + hv * dv) + dense_flops(d, 2 * hv)
           + 2 * m["linear_conv_kernel_dim"] * conv_dim
           + hk * 2 * 2 * (L / 2) * dk                     # K K^T; Q K^T
           + hv * (2 * (L / 2) * (dk + dv)                 # (I + A) \ [K | V]
                   + 3 * 2 * dk * dv                       # W S; Q S; K^T V'
                   + 2 * (L / 2) * dv)                     # (Q K^T) V'
           + dense_flops(hv * dv, d))
    hq = m["num_attention_heads"] * m["head_dim"]
    hkv = m["num_key_value_heads"] * m["head_dim"]
    attn = (dense_flops(d, 2 * hq) + 2 * dense_flops(d, hkv)
            + dense_flops(hq, d) + 2 * 2 * hq * positions / 2)  # q k^T; p v
    load = (m["num_experts_per_tok"] * m["num_experts"]
            / m["num_experts_published"])
    moe = (dense_flops(d, m["num_experts_published"]) + dense_flops(d, 1)
           + 3 * dense_flops(d, m["shared_expert_intermediate_size"])
           + load * 3 * dense_flops(d, m["moe_intermediate_size"]))
    pattern = m["layer_pattern"]
    return {"embed": dense_flops(frame, d),
            "gdn": pattern.count("D") * gdn,
            "attn": pattern.count("*") * attn,
            "moe": pattern.count("E") * moe,
            "head": dense_flops(d, num_actions)}


def update_flops(shapes: dict, state_shape, num_actions: int) -> int:
    """Per position of every segment: the target net's forward, the online
    net's forward and its backward (twice a forward) over ALL T+1
    positions: the burn-in prefix is context, and the gradient flows
    through it.  What ``jax.checkpoint`` computes again is not counted."""
    positions = shapes["seq_len"] + 1
    per_position = sum(forward_flops(
        shapes, positions, state_shape[-2] * state_shape[-1],
        num_actions).values())
    return int(4 * shapes["batch_size"] * positions * per_position)


# -- the program's trunk, walked by the benchmark ------------------------------

def walk(model, params, frames):
    """The program's trunk over (B, T, H, W) frames, block by block through
    models/hybrid.py's own mixers: ({D block: (the block's normed input,
    the delta rule's state after the last position, the same with the
    block's decay slowed by ``SLOW_DECAY_SHIFT``)}, {* block: (its normed
    input, the mixer's output)}).  The states are
    ``HybridQModel.window_pass``'s (benchmark/tests holds them equal); the
    rest is what that pass does not hand out."""
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models import hybrid

    c, cd = model.preset, model.compute_dtype
    tree = params["params"]
    x = hybrid._mm(frames.astype(jnp.float32).reshape(*frames.shape[:2], -1)
                   / model.norm_val, tree["w_embed"], cd).astype(cd)
    B, T, d = x.shape
    states, attended = {}, {}
    for i, kind in enumerate(c.pattern):
        p = tree[f"layers_{i}"]
        u = hybrid.rms_norm(x, hybrid.norm_scale(p["norm"], c), c.norm_eps)
        if kind == "D":
            out, S, _ = hybrid.gdn_window(p, u, c, cd)
            states[i] = (u, S, hybrid.gdn_window(slowed(p), u, c, cd)[1])
        elif kind == "*":
            out = hybrid.attention_window(p, u, c, cd)
            attended[i] = (u, out)
        else:
            out = hybrid.moe_apply(p, u.reshape(B * T, d), c, cd)[0]
            out = out.reshape(B, T, d)
        x = x + out.astype(cd)
    return states, attended


def slowed(layer):
    """A D block's parameters with its decay slowed to a trained model's."""
    return dict(layer, dt_bias=layer["dt_bias"] + SLOW_DECAY_SHIFT)


# -- the check ------------------------------------------------------------------

def reference_hyper(cfg: Dict[str, Any], wrong: Sequence[str] = (),
                    scan_state_dtype: str = "") -> Dict[str, Any]:
    """The configuration's ``reference_hyper`` with the architecture's
    sizes, under the names the reference reads, from its ``shapes``.
    ``wrong`` / ``scan_state_dtype``: a control (the reference with a term
    wrong, or its recurrent state in a lower precision)."""
    s = cfg["shapes"]
    model = {k: s[k] for k in MODEL_KEYS if k in s}
    model["pattern"] = model.pop("layer_pattern")
    model["scan_state_dtype"] = scan_state_dtype or cfg[
        "reference_hyper"].get("scan_state_dtype", "float32")
    model["wrong"] = tuple(wrong)
    return dict(cfg["reference_hyper"], model=model)


def program_side(lrn, seed: int, reference) -> Dict[str, Any]:
    """``nemotron_h.program_side`` for this trunk: one K = 1 fused update of
    the program on the live ring, and what a reference needs to repeat it:
    the batch it drew, the networks it read, what it wrote.  Before it, each
    D block's input over the batch and its states after the last position,
    and the * block's input and output, from ``walk``.  Consumes the train
    state and frees the ring."""
    import jax

    replay, opt = lrn.replay, lrn.opt
    B = opt.agent_params.batch_size
    key = jax.random.PRNGKey(seed ^ 0x5EED)
    beta = np.float32(replay.beta(0))

    sample = replay.sample(B, key, beta=beta)
    side: Dict[str, Any] = {
        "index": np.asarray(sample.index),
        "batch": jax.device_get(reference.batch_of(sample)),
        "pri0": np.asarray(replay.state.priority),
        "fill": int(replay.state.fill),
        "u": np.asarray(jax.random.uniform(key, (B,))),
        "alpha": replay.alpha, "norm_val": lrn.spec.norm_val}
    states = jax.jit(lambda params, frames: walk(lrn.model, params, frames))(
        lrn.state.params, newest_frames(lrn, sample.obs))
    side["gdn"], side["attn"] = ({i: jax.device_get(part[i])
                                  for i in sorted(part)} for part in states)
    del sample, states

    side["params"] = jax.device_get(lrn.state.params)
    side["target"] = jax.device_get(lrn.state.target_params)
    side["mu0"] = jax.device_get(check._first_moment(lrn.state.opt_state))
    fused1 = (getattr(lrn, "fused_step", None) if lrn.K == 1 else None) \
        or program.build_fused(lrn, steps_per_call=1)
    state1, ring1, metrics = fused1(lrn.state, replay.state, key,
                                    jax.device_put(beta))
    side["metrics"] = jax.device_get(metrics)
    side["mu1"] = jax.device_get(check._first_moment(state1.opt_state))
    side["pri1"] = np.asarray(ring1.priority)
    # free the chip before the float32 reference needs it
    lrn.state = replay.state = None
    lrn.fused_step = None
    return side


def compare(side: Dict[str, Any], cfg: Dict[str, Any], reference,
            wrong: Sequence[str] = (), scan_state_dtype: str = ""
            ) -> Dict[str, Any]:
    """The program's update (``program_side``) against the reference's on
    the same batch and networks, judged by the configuration's
    ``tolerance``; with ``wrong`` / ``scan_state_dtype`` against a control.
    ``failed`` names the limits the comparison is outside of."""
    import jax
    import jax.numpy as jnp

    tol = cfg["tolerance"]
    hyper = reference_hyper(cfg, wrong, scan_state_dtype)
    index, pri0, pri1 = side["index"], side["pri0"], side["pri1"]
    metrics = side["metrics"]
    out: Dict[str, Any] = {"sampler": check.cdf_brackets(
        pri0, index, side["u"], side["fill"])}

    loss_ref, signal_ref, grads_ref, rows_ref, aux_ref = reference.update_rows(
        side["params"], side["target"],
        {k: jnp.asarray(v) for k, v in side["batch"].items()}, hyper,
        side["norm_val"])
    signal_ref = np.asarray(signal_ref, np.float64)
    out["grad"] = gradient_agreement(side["mu0"], side["mu1"],
                                     jax.device_get(grads_ref))
    out["grad_cosine"] = out["grad"]["cosine"]
    del grads_ref

    # the weighted balance loss inside the step's loss: 4e-3 at a level load,
    # beside a TD loss that a near-zero Q head makes smaller still (1e-4 in
    # the cell), so each is judged on its own: ``loss`` is the TD part
    aux = float(metrics["learner/moe_aux_loss"])
    aux_ref = float(aux_ref)
    out["aux"] = {"program": aux, "reference": aux_ref,
                  "rel_err": abs(aux - aux_ref) / max(abs(aux), 1e-12)}
    loss = float(metrics["learner/critic_loss"]) - aux
    loss_ref = float(loss_ref) - aux_ref
    out["loss"] = {"program": loss, "reference": loss_ref,
                   "rel_err": abs(loss - loss_ref) / max(abs(loss_ref), 1e-12)}

    rows, counts = np.unique(index, return_counts=True)
    once = np.isin(index, rows[counts == 1])
    signal = pri1[index[once]].astype(np.float64) ** (1.0 / side["alpha"]) \
        - reference.PRIORITY_EPS
    scale = max(float(np.mean(np.abs(signal_ref))), 1e-12)
    err = np.sort(np.abs(signal - signal_ref[once])) / scale
    # the MEDIAN of the segments drawn once, as the nemotron_h family's: an
    # update draws four, and one segment may be off by a whole TD error
    # where bf16 and float32 pick different bootstrap actions
    out["td"] = {"rows": int(err.size),
                 "p50_err_over_mean": float(np.median(err))
                 if err.size else 0.0,
                 "max_err_over_mean": float(err[-1]) if err.size else 0.0}
    changed = np.flatnonzero(pri0 != pri1)
    out["priorities"] = {"rewritten": int(changed.size),
                         "drawn": int(rows.size),
                         "not_drawn": int((~np.isin(changed, rows)).sum())}
    out["skipped"] = float(metrics.get("learner/skipped", 0.0))

    # the program's routing counters against the reference's count
    layers = [i for i, kind in enumerate(cfg["shapes"]["layer_pattern"])
              if kind == "E"]
    here = np.array([float(metrics[f"learner/moe_rows_here/E{i}"])
                     for i in layers])
    here_ref = np.asarray(rows_ref, np.float64).sum(axis=0)
    out["moe"] = {
        "rows_here": here.tolist(), "rows_here_reference": here_ref.tolist(),
        "rows_rel_err": float(np.max(np.abs(here - here_ref)
                                     / np.maximum(here_ref, 1.0))),
        "rows_here_mean": float(metrics["learner/moe_rows_here"]),
        "rows_computed": float(metrics["learner/moe_rows_computed"]),
        "rows_absent_share": float(
            metrics["learner/moe_rows_absent_share"]),
        "load_max_over_mean": float(
            metrics["learner/moe_load_max_over_mean"])}
    out["gdn_decay_mean"] = float(metrics["learner/gdn_decay_mean"])

    # the delta rule's state after the last position, D block by D block,
    # against the reference's block on the SAME input: the one place a
    # state kept in less than float32 can show (the matmuls that read it
    # round to bfloat16 anyway), and it shows only where the state
    # remembers: the second look, with the decay slowed
    layer_of = lambda i: side["params"]["params"][f"layers_{i}"]
    worst = lambda per: {"rel_err_by_layer": per,
                         "rel_err": max(per.values(), default=0.0)}
    out["gdn_state"] = worst({i: rel_err(S, reference.delta_states(
        layer_of(i), u, hyper["model"]))
        for i, (u, S, _) in side["gdn"].items()})
    out["gdn_state_slow"] = worst({i: rel_err(S, reference.delta_states(
        slowed(layer_of(i)), u, hyper["model"]))
        for i, (u, _, S) in side["gdn"].items()})
    # the attention mixer's output against the reference's on the SAME
    # input: positions reach the loss through few of a head's dimensions
    # (theta 1e7 over 2,048 positions), so the gradient hardly sees rotary
    out["attn_out"] = worst({i: rel_err(o, reference.attention_outputs(
        layer_of(i), u, hyper["model"]))
        for i, (u, o) in side["attn"].items()})

    limits = {
        "sampler": out["sampler"]["outside"] == 0
        and out["sampler"]["invalid"] == 0,
        "loss_rel": out["loss"]["rel_err"] <= tol["loss_rel"],
        "aux_rel": out["aux"]["rel_err"] <= tol["aux_rel"],
        "grad_cosine": out["grad_cosine"] >= tol["grad_cosine"],
        "grad_cosine_leaf": out["grad"]["worst_leaf"]["cosine"]
        >= tol["grad_cosine_leaf"],
        "grad_norm_leaf_rel": out["grad"]["worst_norm"]["rel_err"]
        <= tol["grad_norm_leaf_rel"],
        "td_p50_over_mean": out["td"]["p50_err_over_mean"]
        <= tol["td_p50_over_mean"],
        "moe_rows_rel": out["moe"]["rows_rel_err"] <= tol["moe_rows_rel"],
        "gdn_state_rel": out["gdn_state"]["rel_err"] <= tol["gdn_state_rel"],
        "gdn_state_slow_rel": out["gdn_state_slow"]["rel_err"]
        <= tol["gdn_state_slow_rel"],
        "attn_out_rel": out["attn_out"]["rel_err"] <= tol["attn_out_rel"],
        "priorities": out["priorities"]["not_drawn"] == 0
        and out["priorities"]["rewritten"] == rows.size,
        "skipped": out["skipped"] == 0.0}
    out["failed"] = sorted(k for k, ok in limits.items() if not ok)
    out["ok"] = not out["failed"]
    return out


# the controls of the check: the reference with one term wrong, or its
# recurrent state in the precision below the configuration's
CONTROLS = {"bf16_scan_state": dict(scan_state_dtype="bfloat16"),
            **{name: dict(wrong=(name,)) for name in (
                "no_beta", "no_decay", "no_qk_l2norm", "no_attn_gate",
                "no_rotary", "no_topk_renorm", "no_aux")}}


def agrees(lrn, cfg: Dict[str, Any], reference, seed: int) -> Dict[str, Any]:
    """The comparison that decides ``correct``: sampler brackets, loss, the
    balance loss, per-segment priorities, the rows rewritten, the gradient
    formed leaf by leaf (cosine over the tree, the heavy leaf that agrees
    least, leaf norms), the program's count of rows routed to the experts
    held, per E block, each delta-rule block's last state (as it is and
    with its decay slowed) and the attention block's output, against the
    reference's on the same batch and inputs.  Consumes the train state and frees the
    ring."""
    return compare(program_side(lrn, seed, reference), cfg, reference)
