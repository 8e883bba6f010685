"""Family ``nemotron_h``: sequence Q-learning on segments drawn from the HBM
segment ring (``memory/device_sequence.py``, frame-packed; the ring, its
feed, sampler, write-back and seeded segments are the ``r2d2`` family's)
through a hybrid trunk of state-space, sparse-expert and grouped-query
attention layers (``models/hybrid.py``), of which this chip holds some of
each expert layer's experts."""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from ..harness import check, program
from ..harness.shapes import dense_flops
from . import r2d2
from .r2d2 import update_priorities  # noqa: F401

# the published names of the sizes the trunk is built from: the keys of the
# configuration's ``shapes`` group and of its file's top level alike
MODEL_KEYS = (
    "hybrid_override_pattern", "hidden_size", "mamba_num_heads",
    "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel",
    "chunk_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "n_routed_experts_published", "n_routed_experts", "first_expert",
    "num_experts_per_tok", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "routed_scaling_factor",
    "norm_eps", "mlp_hidden_act")


def forward_flops(m: Dict[str, Any], positions: int, frame: int,
                  num_actions: int) -> Dict[str, float]:
    """FLOPs one position's forward pass needs HERE, by layer kind (all
    layers of the kind together), in a window of ``positions``: the experts
    held only, at their expected load (each token's ``num_experts_per_tok``
    choices fall on the ``n_routed_experts`` held of the published count
    uniformly); the causal half of attention; the state-space scan in its
    chunked form (inside a chunk the causal half of the two products
    through the decay matrix, then the chunk's state and its read-out)."""
    d = m["hidden_size"]
    h, p, n = m["mamba_num_heads"], m["mamba_head_dim"], m["ssm_state_size"]
    g, L = m["n_groups"], m["chunk_size"]
    d_inner, conv_dim = h * p, h * p + 2 * g * n
    ssm = (dense_flops(d, 2 * d_inner + 2 * g * n + h)
           + 2 * m["conv_kernel"] * conv_dim
           + 2 * (L / 2) * n * g + 2 * (L / 2) * h * p     # C B^T; (.) x
           + 2 * h * p * n + 2 * h * p * n                 # state; read-out
           + dense_flops(d_inner, d))
    hq = m["num_attention_heads"] * m["head_dim"]
    hk = m["num_key_value_heads"] * m["head_dim"]
    attn = (2 * dense_flops(d, hq) + 2 * dense_flops(d, hk)
            + 2 * 2 * hq * positions / 2)                  # q k^T; p v
    load = (m["num_experts_per_tok"] * m["n_routed_experts"]
            / m["n_routed_experts_published"])
    moe = (dense_flops(d, m["n_routed_experts_published"])
           + 2 * dense_flops(d, m["moe_shared_expert_intermediate_size"])
           + load * 2 * dense_flops(d, m["moe_intermediate_size"]))
    pattern = m["hybrid_override_pattern"]
    return {"embed": dense_flops(frame, d),
            "ssm": pattern.count("M") * ssm,
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


def seed_chunk(key, n: int, lrn):
    """The ``r2d2`` family's segments (one in ten ends early) with frames
    whose positions differ from each other as the source model's token
    embeddings do: a dark field with one pixel in 64 lit (cosine between
    two frames 0.01), not R2D2's uniform bytes (0.75: three quarters of
    every position's vector is the bytes' shared mean) and not pong-sim's
    own frames (0.93: a constant gray field under two paddles and a ball).
    With either of those every token chooses the same experts, Adam moves
    the router faster than ``b_sel`` can follow, and how many of the
    chosen few this chip holds, none or several, is the seed's: no rate
    of a dropless layer repeats there (PERF.md section 6)."""
    import jax
    import jax.numpy as jnp

    chunk = r2d2.seed_chunk(key, n, lrn)
    lit = jax.random.bits(jax.random.fold_in(key, 64), chunk.obs.shape,
                          jnp.uint8) < 4
    return chunk._replace(obs=jnp.where(lit, chunk.obs, jnp.uint8(0)))


# -- the program's trunk, walked by the benchmark ------------------------------

def walk(model, params, frames):
    """The program's trunk over (B, T, H, W) frames, layer by layer through
    models/hybrid.py's own mixers: {M layer: (the layer's normed input,
    the scan's state after the last position)}.  The states are
    ``HybridQModel.window_pass``'s (benchmark/tests holds them equal); the
    inputs are what that pass does not hand out."""
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models import hybrid

    c, cd = model.preset, model.compute_dtype
    tree = params["params"]
    x = hybrid._mm(frames.astype(jnp.float32).reshape(*frames.shape[:2], -1)
                   / model.norm_val, tree["w_embed"], cd).astype(cd)
    B, T, d = x.shape
    states = {}
    for i, kind in enumerate(c.pattern):
        p = tree[f"layers_{i}"]
        u = hybrid.rms_norm(x, p["norm"], c.norm_eps)
        if kind == "M":
            out, S = hybrid.mamba_window(p, u, c, cd)
            states[i] = (u, S)
        elif kind == "*":
            out = hybrid.attention_window(p, u, c, cd)
        else:
            out = hybrid.moe_apply(p, u.reshape(B * T, d), c, cd)[0]
            out = out.reshape(B, T, d)
        x = x + out.astype(cd)
    return states


def newest_frames(lrn, obs):
    """Position t's frame of a sample's obs, frame-packed (B, T + C, H, W)
    as the ring stores it."""
    from pytorch_distributed_tpu import factory

    C = factory.sequence_pack_frames(lrn.opt)
    return obs[:, C - 1:] if C else obs


def build_step(lrn, steps_per_call=None):
    """The learner's fused step, kept on ``lrn`` for ``agrees``: the
    program takes minutes to compile, and at K = 1 the check's one update
    is a call of this same program."""
    lrn.fused_step = program.build_fused(lrn, steps_per_call)
    return lrn.fused_step


# -- the check ------------------------------------------------------------------

def reference_hyper(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration's ``reference_hyper`` with the architecture's
    sizes, under the names the reference reads, from its ``shapes``."""
    s = cfg["shapes"]
    model = {k: s[k] for k in MODEL_KEYS if k in s}
    model["pattern"] = s["hybrid_override_pattern"]
    model["scan_state_dtype"] = cfg["reference_hyper"].get(
        "scan_state_dtype", "float32")
    return dict(cfg["reference_hyper"], model=model)


def rel_err(a, b) -> float:
    """|a - b| / |b| over whole arrays, in float64."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.sum(np.square(a - b))
                         / max(np.sum(np.square(b)), 1e-300)))


def gradient_agreement(mu0, mu1, grads_ref) -> Dict[str, Any]:
    """The program's gradient, recovered leaf by leaf from the change of
    Adam's first moment, against the reference's: the cosine over the whole
    tree, and among the leaves that carry more than a millionth of the
    gradient's squared norm the least cosine and the norm furthest from
    the reference's (a factor missing from one layer's output scales that
    layer's leaves and turns none).  One leaf at a time in float64: the
    tree holds 0.6 G numbers."""
    import jax

    names = ["/".join(str(getattr(k, "key", k)) for k in path)
             for path, _ in jax.tree_util.tree_flatten_with_path(mu0)[0]]
    dot = pp = rr = 0.0
    per_leaf = []
    for name, a, b, r in zip(names, jax.tree_util.tree_leaves(mu0),
                             jax.tree_util.tree_leaves(mu1),
                             jax.tree_util.tree_leaves(grads_ref)):
        g = (np.asarray(b, np.float64) - check.ADAM_B1
             * np.asarray(a, np.float64)) / (1.0 - check.ADAM_B1)
        r = np.asarray(r, np.float64)
        d, p, q = float(np.vdot(g, r)), float(np.vdot(g, g)), float(
            np.vdot(r, r))
        dot, pp, rr = dot + d, pp + p, rr + q
        per_leaf.append((name, d / max(np.sqrt(p * q), 1e-300),
                         np.sqrt(p / max(q, 1e-300)), q))
    heavy = [leaf for leaf in per_leaf if leaf[3] > 1e-6 * rr]
    turned = min(heavy, key=lambda leaf: leaf[1], default=("", 1.0, 1.0))
    scaled = max(heavy, key=lambda leaf: abs(np.log(max(leaf[2], 1e-300))),
                 default=("", 1.0, 1.0))
    return {"cosine": dot / max(np.sqrt(pp * rr), 1e-300),
            "norm": {"program": np.sqrt(pp), "reference": np.sqrt(rr)},
            "worst_leaf": {"name": turned[0], "cosine": turned[1]},
            "worst_norm": {"name": scaled[0], "program_over_reference":
                           scaled[2], "rel_err": abs(scaled[2] - 1.0)},
            "leaves": len(per_leaf)}


def program_side(lrn, seed: int, reference) -> Dict[str, Any]:
    """One K = 1 fused update of the program on the live ring, and what a
    reference needs to repeat it: the batch it drew, the networks it read,
    what it wrote.  Before it, each M layer's input over the batch and
    its scan's state after the last position, from ``walk``.  Consumes the
    train state and frees the ring."""
    import jax
    import jax.numpy as jnp

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
    side["ssm"] = {i: jax.device_get(states[i]) for i in sorted(states)}
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
            params=None, target=None) -> Dict[str, Any]:
    """The program's update (``program_side``) against the reference's on
    the same batch and networks (or on ``params`` / ``target``: a control),
    judged by the configuration's ``tolerance``."""
    import jax
    import jax.numpy as jnp

    tol = cfg["tolerance"]
    hyper = reference_hyper(cfg)
    index, pri0, pri1 = side["index"], side["pri0"], side["pri1"]
    metrics = side["metrics"]
    params = side["params"] if params is None else params
    target = side["target"] if target is None else target
    out: Dict[str, Any] = {"sampler": check.cdf_brackets(
        pri0, index, side["u"], side["fill"])}

    loss_ref, signal_ref, grads_ref, rows_ref = reference.update_rows(
        params, target, {k: jnp.asarray(v) for k, v in side["batch"].items()},
        hyper, side["norm_val"])
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
    # judged on the MEDIAN of the segments drawn once: an update draws four,
    # so R2D2's 90th percentile would be the maximum, and one segment may
    # be off by a whole TD error where bf16 and float32 pick different
    # bootstrap actions (seen on the chip, PR 26: 7.4 % in one run of five,
    # the other segments and runs under 0.4 %); a wrong term moves them all
    out["td"] = {"rows": int(err.size),
                 "p50_err_over_mean": float(np.median(err))
                 if err.size else 0.0,
                 "p90_err_over_mean": float(
                     err[max(0, int(np.ceil(0.9 * err.size)) - 1)])
                 if err.size else 0.0,
                 "max_err_over_mean": float(err[-1]) if err.size else 0.0}
    changed = np.flatnonzero(pri0 != pri1)
    out["priorities"] = {"rewritten": int(changed.size),
                         "drawn": int(rows.size),
                         "not_drawn": int((~np.isin(changed, rows)).sum())}
    out["skipped"] = float(metrics.get("learner/skipped", 0.0))

    # the program's routing counters against the reference's count
    layers = [i for i, kind in enumerate(
        cfg["shapes"]["hybrid_override_pattern"]) if kind == "E"]
    here = np.array([float(metrics[f"learner/moe_rows_here/E{i}"])
                     for i in layers])
    here_ref = np.asarray(rows_ref, np.float64).sum(axis=0)
    out["moe"] = {
        "rows_here": here.tolist(), "rows_here_reference": here_ref.tolist(),
        "rows_rel_err": float(np.max(np.abs(here - here_ref)
                                     / np.maximum(here_ref, 1.0))),
        "rows_absent_share": float(
            metrics["learner/moe_rows_absent_share"]),
        "load_max_over_mean": float(
            metrics["learner/moe_load_max_over_mean"])}

    # the scan's state after the last position, M layer by M layer, against
    # the reference's layer on the SAME input: the one place a state kept in
    # less than float32 shows (it is read through bfloat16 matmuls, whose
    # rounding hides it everywhere downstream)
    per_layer = {i: rel_err(S, reference.mamba_states(
        params["params"][f"layers_{i}"], u, hyper["model"]))
        for i, (u, S) in side["ssm"].items()}
    out["ssm_state"] = {"rel_err_by_layer": per_layer,
                        "rel_err": max(per_layer.values(), default=0.0)}

    out["ok"] = bool(
        out["sampler"]["outside"] == 0 and out["sampler"]["invalid"] == 0
        and out["loss"]["rel_err"] <= tol["loss_rel"]
        and out["grad_cosine"] >= tol["grad_cosine"]
        and out["grad"]["worst_leaf"]["cosine"] >= tol["grad_cosine_leaf"]
        and out["grad"]["worst_norm"]["rel_err"] <= tol["grad_norm_leaf_rel"]
        and out["td"]["p50_err_over_mean"] <= tol["td_p50_over_mean"]
        and out["moe"]["rows_rel_err"] <= tol["moe_rows_rel"]
        and out["ssm_state"]["rel_err"] <= tol["ssm_state_rel"]
        and out["priorities"]["not_drawn"] == 0
        and out["priorities"]["rewritten"] == rows.size
        and out["skipped"] == 0.0)
    return out


def agrees(lrn, cfg: Dict[str, Any], reference, seed: int) -> Dict[str, Any]:
    """``check.fused_update_agrees`` for a train state of 0.6 G parameters:
    the same comparisons (sampler brackets, loss, per-segment priorities,
    the rows rewritten, gradient cosine), the gradient formed leaf by leaf,
    plus the program's count of rows routed to the experts held, per E
    layer, and its scans' last states, against the reference's on the same
    batch.  Consumes the train state and frees the ring."""
    return compare(program_side(lrn, seed, reference), cfg, reference)
