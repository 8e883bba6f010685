"""Family ``kimi_linear``: sequence Q-learning on segments drawn from the HBM
segment ring through the third hybrid trunk of ``models/hybrid.py``
(``PRESETS["kimi-linear-5"]``): channel-gated delta-rule mixers (Kimi Delta
Attention), one position-free latent-attention mixer, a leading dense SwiGLU
block and sigmoid-routed SwiGLU expert blocks with an ungated shared expert,
of which this chip holds some of each block's experts.  The ring, its feed,
sampler, write-back and the sparse seeded frames are the ``r2d2`` and
``nemotron_h`` families'."""

from __future__ import annotations

import functools
from typing import Any, Dict, Sequence

import numpy as np

from ..harness import check, program
from ..harness.shapes import dense_flops
from .nemotron_h import (build_step, gradient_agreement,  # noqa: F401
                         newest_frames, rel_err, seed_chunk,
                         update_priorities)

# the names of the sizes the trunk is built from: the keys of the
# configuration's ``shapes`` group, the published ones also at its file's top
# level (``kda_num_heads`` / ``kda_head_dim`` / ``short_conv_kernel_size``
# there inside ``linear_attn_config``)
MODEL_KEYS = (
    "layer_pattern", "hidden_size", "kda_num_heads", "kda_head_dim",
    "short_conv_kernel_size", "kda_gate_rank", "kda_chunk", "kda_sub_block",
    "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "kv_lora_rank", "intermediate_size",
    "num_experts_published", "num_experts", "first_expert",
    "num_experts_per_token", "moe_intermediate_size",
    "shared_expert_intermediate_size", "moe_renormalize",
    "routed_scaling_factor", "rms_norm_eps")


# added to a K block's ``dt_bias`` for the check's second look at its
# recurrence: at the seeded init (``A`` ~ U(1, 16), ``dt`` log-uniform in
# [1e-3, 0.1]) a channel keeps 0.83 a position in the mean and a bfloat16
# state is twice the program's own distance from a float32 one; at -4 every
# channel keeps 0.99 or more, a trained model's long memory, and the state's
# precision shows four times over (PERF.md section 6, PR 34)
SLOW_DECAY_SHIFT = -4.0


def forward_flops(m: Dict[str, Any], positions: int, frame: int,
                  num_actions: int) -> Dict[str, float]:
    """FLOPs one position's forward pass needs HERE, by block kind (all
    blocks of the kind together), in a window of ``positions``: the experts
    held only, at their expected load; the causal half of the latent
    attention's scores and values, from EXPANDED keys (a head's 192) and
    values (128); the delta rule in its chunked form AS THE ALGORITHM NEEDS
    IT, counted as ``families/qwen3_next.py`` counts the scalar-gated one
    (a head's gate or a channel's, the products are the same): inside a
    chunk of L the causal half of ``K K^T`` and ``Q K^T`` a head, the
    triangular system solved against keys and values (the inverse the
    program forms by ten dense L x L products is its own affair and is NOT
    counted, as there), ``W S``, ``Q S``, ``(Q K^T) V'`` and ``K^T V'``."""
    d = m["hidden_size"]
    h, dk, L = m["kda_num_heads"], m["kda_head_dim"], m["kda_chunk"]
    rank = m["kda_gate_rank"]
    kda = (3 * dense_flops(d, h * dk)
           + 2 * (dense_flops(d, rank) + dense_flops(rank, h * dk))
           + dense_flops(d, h)
           + 3 * 2 * m["short_conv_kernel_size"] * h * dk
           + h * (2 * 2 * (L / 2) * dk                     # K K^T; Q K^T
                  + 2 * (L / 2) * 2 * dk                   # (I + A) \ [K | V]
                  + 3 * 2 * dk * dk                        # W S; Q S; K^T V'
                  + 2 * (L / 2) * dk)                      # (Q K^T) V'
           + dense_flops(h * dk, d))
    heads = m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    mla = (dense_flops(d, heads * qk)
           + dense_flops(d, m["kv_lora_rank"] + m["qk_rope_head_dim"])
           + dense_flops(m["kv_lora_rank"],
                         heads * (m["qk_nope_head_dim"] + m["v_head_dim"]))
           + dense_flops(heads * m["v_head_dim"], d)
           + 2 * heads * (qk + m["v_head_dim"]) * positions / 2)
    mlp = 3 * dense_flops(d, m["intermediate_size"])
    load = (m["num_experts_per_token"] * m["num_experts"]
            / m["num_experts_published"])
    moe = (dense_flops(d, m["num_experts_published"])
           + 3 * dense_flops(d, m["shared_expert_intermediate_size"])
           + load * 3 * dense_flops(d, m["moe_intermediate_size"]))
    pattern = m["layer_pattern"]
    return {"embed": dense_flops(frame, d),
            "kda": pattern.count("K") * kda,
            "mla": pattern.count("L") * mla,
            "mlp": pattern.count("F") * mlp,
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


# -- the program's trunk, walked by the benchmark -----------------------------

def walk(model, params, frames):
    """The program's trunk over (B, T, H, W) frames, block by block through
    models/hybrid.py's own mixers: ({K block: (the block's normed input, the
    delta rule's state after the last position, the same with the block's
    decay slowed by ``SLOW_DECAY_SHIFT``)}, {L block: (its normed input, the
    mixer's output)}).  The states are ``HybridQModel.window_pass``'s
    (benchmark/tests holds them equal); the rest is what that pass does not
    hand out.  A program a block: beside a live train state the chip has no
    room for ten blocks' intermediates at once."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models import hybrid

    c, cd = model.preset, model.compute_dtype
    tree = params["params"]

    @jax.jit
    def embed(w, frames):
        x = frames.astype(jnp.float32).reshape(*frames.shape[:2], -1)
        return hybrid._mm(x / model.norm_val, w, cd).astype(cd)

    @functools.partial(jax.jit, static_argnames="kind")
    def block(p, x, kind):
        B, T, d = x.shape
        u = hybrid.rms_norm(x, hybrid.norm_scale(p["norm"], c), c.norm_eps)
        kept = ()
        if kind == "K":
            out, S, _ = hybrid.kda_window(p, u, c, cd)
            kept = (u, S, hybrid.kda_window(slowed(p), u, c, cd)[1])
        elif kind == "L":
            out = hybrid.mla_window(p, u, c, cd)
            kept = (u, out)
        elif kind == "F":
            out = hybrid.mlp_block(p, u, cd)
        else:
            out = hybrid.moe_apply(p, u.reshape(B * T, d), c, cd)[0]
            out = out.reshape(B, T, d)
        return x + out.astype(cd), kept

    x = embed(tree["w_embed"], frames)
    kept = {}
    for i, kind in enumerate(c.pattern):
        x, kept[i] = block(tree[f"layers_{i}"], x, kind)
    return ({i: kept[i] for i, kind in enumerate(c.pattern) if kind == "K"},
            {i: kept[i] for i, kind in enumerate(c.pattern) if kind == "L"})


def slowed(layer):
    """A K block's parameters with its decay slowed to a trained model's."""
    return dict(layer, dt_bias=layer["dt_bias"] + SLOW_DECAY_SHIFT)


# -- the check ----------------------------------------------------------------

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
    K block's input over the batch and its states after the last position,
    and the L block's input and output, from ``walk``.  Consumes the train
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
    states = walk(lrn.model, lrn.state.params,
                  newest_frames(lrn, sample.obs))
    side["kda"], side["mla"] = ({i: jax.device_get(part[i])
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

    loss_ref, signal_ref, grads_ref, rows_ref = reference.update_rows(
        side["params"], side["target"],
        {k: jnp.asarray(v) for k, v in side["batch"].items()}, hyper,
        side["norm_val"])
    loss_ref = float(loss_ref)
    signal_ref = np.asarray(signal_ref, np.float64)
    out["grad"] = gradient_agreement(side["mu0"], side["mu1"],
                                     jax.device_get(grads_ref))
    out["grad_cosine"] = out["grad"]["cosine"]
    del grads_ref

    loss = float(metrics["learner/critic_loss"])
    out["loss"] = {"program": loss, "reference": loss_ref,
                   "rel_err": abs(loss - loss_ref) / max(abs(loss_ref), 1e-12)}

    rows, counts = np.unique(index, return_counts=True)
    once = np.isin(index, rows[counts == 1])
    signal = pri1[index[once]].astype(np.float64) ** (1.0 / side["alpha"]) \
        - reference.PRIORITY_EPS
    scale = max(float(np.mean(np.abs(signal_ref))), 1e-12)
    err = np.sort(np.abs(signal - signal_ref[once])) / scale
    # the MEDIAN of the segments drawn once, as the other hybrid families':
    # an update draws four, and one segment may be off by a whole TD error
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
    out["kda_decay"] = {"mean": float(metrics["learner/kda_decay_mean"]),
                        "min": float(metrics["learner/kda_decay_min"])}

    # the delta rule's state after the last position, K block by K block,
    # against the reference's block on the SAME input: where a gate that is
    # a head's and not a channel's, a missing beta or a state kept in less
    # than float32 shows first; the second look, with the decay slowed, is
    # where a long memory makes the state's precision plain
    layer_of = lambda i: side["params"]["params"][f"layers_{i}"]
    worst = lambda per: {"rel_err_by_layer": per,
                         "rel_err": max(per.values(), default=0.0)}
    out["kda_state"] = worst({i: rel_err(S, reference.delta_states(
        layer_of(i), u, hyper["model"]))
        for i, (u, S, _) in side["kda"].items()})
    out["kda_state_slow"] = worst({i: rel_err(S, reference.delta_states(
        slowed(layer_of(i)), u, hyper["model"]))
        for i, (u, _, S) in side["kda"].items()})
    # the latent attention's output against the reference's on the SAME
    # input: one block of ten, whose parts the gradient sees through nine
    out["mla_out"] = worst({i: rel_err(o, reference.latent_outputs(
        layer_of(i), u, hyper["model"]))
        for i, (u, o) in side["mla"].items()})

    limits = {
        "sampler": out["sampler"]["outside"] == 0
        and out["sampler"]["invalid"] == 0,
        "loss_rel": out["loss"]["rel_err"] <= tol["loss_rel"],
        "grad_cosine": out["grad_cosine"] >= tol["grad_cosine"],
        "grad_cosine_leaf": out["grad"]["worst_leaf"]["cosine"]
        >= tol["grad_cosine_leaf"],
        "grad_norm_leaf_rel": out["grad"]["worst_norm"]["rel_err"]
        <= tol["grad_norm_leaf_rel"],
        "td_p50_over_mean": out["td"]["p50_err_over_mean"]
        <= tol["td_p50_over_mean"],
        "moe_rows_rel": out["moe"]["rows_rel_err"] <= tol["moe_rows_rel"],
        "kda_state_rel": out["kda_state"]["rel_err"] <= tol["kda_state_rel"],
        "kda_state_slow_rel": out["kda_state_slow"]["rel_err"]
        <= tol["kda_state_slow_rel"],
        "mla_out_rel": out["mla_out"]["rel_err"] <= tol["mla_out_rel"],
        "priorities": out["priorities"]["not_drawn"] == 0
        and out["priorities"]["rewritten"] == rows.size,
        "skipped": out["skipped"] == 0.0}
    out["failed"] = sorted(k for k, ok in limits.items() if not ok)
    out["ok"] = not out["failed"]
    return out


# the controls of the check: the reference with one term wrong
# (``reference.WRONG``), or its recurrent state in the precision below the
# configuration's
CONTROLS = {"bf16_scan_state": dict(scan_state_dtype="bfloat16"),
            **{name: dict(wrong=(name,)) for name in (
                "head_mean_decay", "no_beta", "no_latent_norm",
                "key_part_a_head", "no_topk_renorm", "no_route_scale")}}


def agrees(lrn, cfg: Dict[str, Any], reference, seed: int) -> Dict[str, Any]:
    """The comparison that decides ``correct``: sampler brackets, loss,
    per-segment priorities, the rows rewritten, the gradient formed leaf by
    leaf (cosine over the tree, the heavy leaf that agrees least, leaf
    norms), the program's count of rows routed to the experts held, per E
    block, each delta-rule block's last state (as it is and with its decay
    slowed) and the latent attention's output, against the reference's on
    the same batch and inputs.  Consumes the train state and frees the
    ring."""
    return compare(program_side(lrn, seed, reference), cfg, reference)
