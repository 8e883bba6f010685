"""Family ``lfm2_moe``: sequence Q-learning on segments drawn from the HBM
segment ring through the fourth hybrid trunk of ``models/hybrid.py``
(``PRESETS["lfm2-moe-5"]``): gated short-convolution mixers, one
grouped-query attention with query / key norms and rotary on the whole
head, a leading dense SwiGLU block and sigmoid-routed SwiGLU expert blocks
with no shared expert, of which this chip holds some of each block's
experts.  The ring, its feed, sampler, write-back and the sparse seeded
frames are the ``r2d2`` and ``nemotron_h`` families'; the gradient's
agreement and the step program are ``nemotron_h``'s."""

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
# level
MODEL_KEYS = (
    "layer_pattern", "hidden_size", "conv_L_cache", "num_attention_heads",
    "num_key_value_heads", "head_dim", "rope_theta", "intermediate_size",
    "num_experts_published", "num_experts", "first_expert",
    "num_experts_per_tok", "moe_intermediate_size", "norm_topk_prob",
    "routed_scaling_factor", "router_eps", "norm_eps")
# the blocks whose output the check compares with the reference's on the
# same input, and the limit each is judged by; an expert block's routing
# weights besides (``route_weight``)
COMPARED = {"C": "sconv_out", "*": "attn_out", "E": "moe_out"}
# the Q head's leaves: their gradient is a sum of the segments' TD errors,
# which nearly cancel, so a bfloat16 trunk moves its norm by a few per cent
# either way (0.4-4.7 % in eleven sound runs on the chip, PERF.md section
# 6); the TD checks and the cosine judge them, the leaf norms the trunk's
HEAD = ("head_w", "head_b")


def forward_flops(m: Dict[str, Any], positions: int, frame: int,
                  num_actions: int) -> Dict[str, float]:
    """FLOPs one position's forward pass needs HERE, by block kind (all
    blocks of the kind together), in a window of ``positions``: the experts
    held only, at their expected load (each token's ``num_experts_per_tok``
    choices fall on the ``num_experts`` held of the published count
    uniformly); the causal half of attention's scores and values; the short
    convolution's three projections, its taps and its two gates."""
    d = m["hidden_size"]
    sconv = (dense_flops(d, 3 * d) + 2 * m["conv_L_cache"] * d
             + 2 * d + dense_flops(d, d))
    hq = m["num_attention_heads"] * m["head_dim"]
    hk = m["num_key_value_heads"] * m["head_dim"]
    attn = (2 * dense_flops(d, hq) + 2 * dense_flops(d, hk)
            + 2 * 2 * hq * positions / 2)                  # q k^T; p v
    mlp = 3 * dense_flops(d, m["intermediate_size"])
    load = (m["num_experts_per_tok"] * m["num_experts"]
            / m["num_experts_published"])
    moe = (dense_flops(d, m["num_experts_published"])
           + load * 3 * dense_flops(d, m["moe_intermediate_size"]))
    pattern = m["layer_pattern"]
    return {"embed": dense_flops(frame, d),
            "sconv": pattern.count("C") * sconv,
            "attn": pattern.count("*") * attn,
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
    models/hybrid.py's own mixers: {block index: (the block's normed input,
    its mixer's output, and for an expert block the experts each position
    chose and their weights)} for the short-convolution, attention and
    expert blocks.  A program a block: beside a live train state the chip
    has no room for ten blocks' intermediates at once."""
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
        routed = ()
        if kind == "C":
            out = hybrid.short_conv_window(p, u, c, cd)
        elif kind == "*":
            out = hybrid.attention_window(p, u, c, cd)
        elif kind == "F":
            out = hybrid.mlp_block(p, u, cd)
        else:
            tokens = u.reshape(B * T, d)
            out = hybrid.moe_apply(p, tokens, c, cd)[0].reshape(B, T, d)
            routed = tuple(t.reshape(B, T, -1)
                           for t in hybrid.route(p, tokens, c)[:2])
        kept = (u, out) + routed if kind in COMPARED else ()
        return x + out.astype(cd), kept

    x = embed(tree["w_embed"], frames)
    kept = {}
    for i, kind in enumerate(c.pattern):
        x, kept[i] = block(tree[f"layers_{i}"], x, kind)
    return {i: kept[i] for i, kind in enumerate(c.pattern)
            if kind in COMPARED}


# -- the check ----------------------------------------------------------------

def reference_hyper(cfg: Dict[str, Any], wrong: Sequence[str] = (),
                    dtype: str = "") -> Dict[str, Any]:
    """The configuration's ``reference_hyper`` with the architecture's
    sizes, under the names the reference reads, from its ``shapes``.
    ``wrong`` / ``dtype``: a control (the reference with a term wrong, or
    the whole trunk in a lower precision)."""
    s = cfg["shapes"]
    model = {k: s[k] for k in MODEL_KEYS if k in s}
    model["pattern"] = model.pop("layer_pattern")
    model["dtype"] = dtype or "float32"
    model["wrong"] = tuple(wrong)
    return dict(cfg["reference_hyper"], model=model)


def without_head(tree):
    """A parameter-shaped tree without the Q head's leaves (``HEAD``)."""
    return {"params": {k: v for k, v in tree["params"].items()
                       if k not in HEAD}}


def program_side(lrn, seed: int, reference) -> Dict[str, Any]:
    """``nemotron_h.program_side`` for this trunk: one K = 1 fused update of
    the program on the live ring, and what a reference needs to repeat it:
    the batch it drew, the networks it read, what it wrote.  Before it, the
    normed input and the output of each short-convolution, attention and
    expert block over the batch, from ``walk``.  Consumes the train state
    and frees the ring."""
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
    blocks = walk(lrn.model, lrn.state.params,
                  newest_frames(lrn, sample.obs))
    side["blocks"] = {i: jax.device_get(blocks[i]) for i in sorted(blocks)}
    del sample, blocks

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
            wrong: Sequence[str] = (), dtype: str = "") -> Dict[str, Any]:
    """The program's update (``program_side``) against the reference's on
    the same batch and networks, judged by the configuration's
    ``tolerance``; with ``wrong`` / ``dtype`` against a control.
    ``failed`` names the limits the comparison is outside of."""
    import jax
    import jax.numpy as jnp

    tol = cfg["tolerance"]
    hyper = reference_hyper(cfg, wrong, dtype)
    pattern = cfg["shapes"]["layer_pattern"]
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
    grads_ref = jax.device_get(grads_ref)
    out["grad"] = gradient_agreement(side["mu0"], side["mu1"], grads_ref)
    out["grad"]["worst_norm_with_head"] = out["grad"]["worst_norm"]
    out["grad"]["worst_norm"] = gradient_agreement(*(
        without_head(t) for t in (side["mu0"], side["mu1"], grads_ref))
    )["worst_norm"]
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
    layers = [i for i, kind in enumerate(pattern) if kind == "E"]
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

    # each short-convolution, attention and expert block's output against
    # the reference's block on the SAME input: where a gate, a tap, a norm,
    # the rotary or an expert is wrong in one block of ten, and the gradient
    # through the other nine hardly sees it; and an expert block's weights
    # of the experts the program chose, against the reference's weights of
    # the same experts: a selection bias in the weights or a sum left out
    # moves them by a few per cent, and a bfloat16 router by as much,
    # beside a float32 router's 1e-6
    layer_of = lambda i: side["params"]["params"][f"layers_{i}"]
    worst = lambda per: {"rel_err_by_layer": per,
                         "rel_err": max(per.values(), default=0.0)}
    for kind, name in COMPARED.items():
        out[name] = worst({i: rel_err(o, reference.block_outputs(
            layer_of(i), u, kind, hyper["model"]))
            for i, (u, o, *_) in side["blocks"].items()
            if pattern[i] == kind})
    out["route_weight"] = worst({i: rel_err(w, reference.chosen_weights(
        layer_of(i), u, chosen, hyper["model"]))
        for i, (u, _, chosen, w) in (
            (i, b) for i, b in side["blocks"].items() if len(b) == 4)})

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
        **{f"{name}_rel": out[name]["rel_err"] <= tol[f"{name}_rel"]
           for name in (*COMPARED.values(), "route_weight")},
        "priorities": out["priorities"]["not_drawn"] == 0
        and out["priorities"]["rewritten"] == rows.size,
        "skipped": out["skipped"] == 0.0}
    out["failed"] = sorted(k for k, ok in limits.items() if not ok)
    out["ok"] = not out["failed"]
    return out


# the controls of the check: the reference with one term wrong
# (``reference.WRONG``), or the whole trunk in the precision below the
# configuration's
CONTROLS = {"bf16_trunk": dict(dtype="bfloat16"),
            **{name: dict(wrong=(name,)) for name in (
                "no_conv_gate", "conv_shift", "b_sel_in_weights",
                "no_qk_norm", "half_rotary", "no_topk_renorm")}}


def agrees(lrn, cfg: Dict[str, Any], reference, seed: int) -> Dict[str, Any]:
    """The comparison that decides ``correct``: sampler brackets, loss,
    per-segment priorities, the rows rewritten, the gradient formed leaf by
    leaf (cosine over the tree, the heavy leaf that agrees least, the
    trunk's leaf norms), the program's count of rows routed to the experts
    held, per E block, each short-convolution, attention and expert block's
    output and each expert block's routing weights, against the reference's
    on the same batch and inputs.  Consumes the
    train state and frees the ring."""
    return compare(program_side(lrn, seed, reference), cfg, reference)
