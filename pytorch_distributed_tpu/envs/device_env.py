"""Device-resident vectorized envs: the pure-JAX environment protocol.

The actor plane's throughput ceiling so far has been the HOST env step:
``envs/vector.py`` advances N Python simulators one ``step()`` at a time
(and ``native_pong.py`` one C call at a time), so every tick pays N
Python frames of work and the policy's device dispatch round-trips the
obs through host memory, while the chip idles waiting for experience
(how far behind the host plane runs on a directly attached chip is not
measured).  Podracer (Hessel et al. 2021) names the
fix: put the environments ON the device as pure functions and advance
thousands of them per XLA dispatch, fused with the policy step (the
Sebulba/Anakin actor plane).

This module supplies:

- ``DeviceEnv`` — the protocol: an env family as three pure functions
  (``init``/``step``/``observe``) over a batched state pytree, plus the
  static metadata the models/replay need.  ``step`` applies auto-reset
  internally and ALWAYS returns the true post-step observation
  (``final_obs``) next to the reset one, so the n-step assembler sees
  real episode boundaries — the same contract ``envs/vector.py``
  documents with its ``info["final_obs"]`` stash.

- ``make_device_pong`` — a Pong implementation ported op-for-op from
  ``envs/pong_sim.py`` (same 84x84 uint8 pipeline: action-repeat with a
  2-frame maxpool, hist-length stack, rate-limited tracker opponent,
  scoring to 21, ``early_stop`` truncation).  The kernel is written
  once over an array-module parameter ``xp`` so the SAME code runs as
  jitted jnp on the device and as plain numpy on the host — the host
  execution is the parity oracle (tests/test_device_env.py): f32 numpy
  and f32 XLA must agree bit-for-bit over full episodes, and the f64
  numpy run must agree bit-for-bit with the real ``PongSimEnv`` class
  once its RNG draws are replayed (see ``CounterRng``).

- ``DevicePongVectorEnv`` — a drop-in for ``envs.vector.VectorEnv``
  driving the jitted device step from the host loop, so the existing
  inline/pipelined actor backends (and the parity tests) can run
  against the device env without the fused rollout engine.

Randomness: the host sim draws from numpy's PCG64, which no XLA program
can reproduce.  The device env instead derives every draw from a
counter-based uint32 hash of ``(slot_seed, draw_index)`` (splitmix32
avalanche) — a pure function both numpy and jnp evaluate identically,
and one the parity oracle can replay into the host ``PongSimEnv``
class.  Slot seeding follows the fleet contract: env j of actor i takes
slot ``seed + i*N + j`` (factory.build_env_vector), so backend choice
never changes the seed stream.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, NamedTuple, Tuple

import numpy as np

from pytorch_distributed_tpu.envs.base import DiscreteSpace
from pytorch_distributed_tpu.envs.pong_sim import (
    BALL_SPEED_X, ENEMY_SPEED, WIN_SCORE,
)

# ---------------------------------------------------------------------------
# counter-based RNG: a pure function of (slot_seed, draw_index)
# ---------------------------------------------------------------------------

_MIX1 = 0x7FEB352D
_MIX2 = 0x846CA68B
_SEED_GOLD = 0x9E3779B9  # Weyl constant decorrelating adjacent slot seeds


def counter_mix(seed, count, xp=np):
    """splitmix32-style avalanche of ``seed ^ (count * golden)`` on
    uint32 arrays — identical wraparound semantics in numpy and jnp."""
    u = np.uint32
    x = xp.asarray(seed, np.uint32) ^ (
        xp.asarray(count, np.uint32) * u(_SEED_GOLD))
    x = (x ^ (x >> u(16))) * u(_MIX1)
    x = (x ^ (x >> u(15))) * u(_MIX2)
    return x ^ (x >> u(16))


def counter_uniform(seed, count, lo, hi, xp=np, dtype=np.float32):
    """``lo + (hi - lo) * u`` with ``u`` in [0, 1) from the top 24 hash
    bits (exactly representable in f32, so the f32 and f64 runs see the
    same u)."""
    u = (counter_mix(seed, count, xp) >> np.uint32(8)).astype(dtype) \
        * dtype(1.0 / (1 << 24))
    return dtype(lo) + (dtype(hi) - dtype(lo)) * u


class CounterRng:
    """Host-side shim with the numpy-Generator surface ``PongSimEnv``
    draws from (``uniform``, ``random``), replaying the device env's
    counter stream — patched into a ``PongSimEnv`` instance by the
    parity oracle so the REAL host class walks the exact episode the
    device env walks."""

    def __init__(self, seed: int):
        self.seed = np.uint32(seed)
        self.count = 0

    def uniform(self, lo: float, hi: float) -> float:
        self.count += 1
        return float(counter_uniform(
            np.asarray([self.seed], np.uint32),
            np.asarray([self.count], np.uint32),
            lo, hi, xp=np, dtype=np.float64)[0])

    def random(self) -> float:
        return self.uniform(0.0, 1.0)


# ---------------------------------------------------------------------------
# the protocol
# ---------------------------------------------------------------------------

class PongState(NamedTuple):
    """Batched per-env state (leading dim N everywhere)."""

    player_y: Any
    enemy_y: Any
    ball_x: Any
    ball_y: Any
    ball_vx: Any
    ball_vy: Any
    score_enemy: Any     # (N,) int32
    score_player: Any    # (N,) int32
    episode_steps: Any   # (N,) int32
    rng_count: Any       # (N,) uint32 draw counter
    seed: Any            # (N,) uint32 slot seed (constant)
    stack: Any           # (N, hist, 84, 84) uint8 current obs


class StepOut(NamedTuple):
    """One batched env step.  ``obs`` is the post-step observation with
    auto-reset applied; ``final_obs`` is the TRUE post-step stack (the
    terminal frames where ``terminal``, identical to ``obs``
    elsewhere) — the ``info["final_obs"]`` of the host vector env as a
    dense array."""

    obs: Any           # (N, hist, 84, 84) uint8
    final_obs: Any     # (N, hist, 84, 84) uint8
    reward: Any        # (N,) f32
    terminal: Any      # (N,) bool
    truncated: Any     # (N,) bool
    score: Any         # (N, 2) int32 (enemy, player)


@dataclass(frozen=True)
class DeviceEnv:
    """An env family as pure functions over a batched state pytree.

    ``init()`` builds the reset state for all N envs; ``step(state,
    actions)`` advances every env one agent step (auto-reset inside);
    ``observe(state)`` reads the current observation without stepping.
    ``step`` must be jit/vmap/scan-safe: no host callbacks, fixed
    shapes, randomness from counters carried in the state.
    """

    num_envs: int
    state_shape: Tuple[int, ...]
    num_actions: int
    norm_val: float
    init: Callable[[], Any]
    step: Callable[[Any, Any], Tuple[Any, StepOut]]
    observe: Callable[[Any], Any]


# ---------------------------------------------------------------------------
# Pong, transcribed from envs/pong_sim.py
#
# Every float constant below is the evaluated form of the pong_sim
# expression it mirrors (PADDLE_H/2 = 5.0, H - PADDLE_H/2 = 79.0,
# BALL/2 = 1.0, 2*(H - BALL/2) = 166.0, PLAYER_X - PADDLE_W = 76.0,
# ENEMY_X + PADDLE_W = 6.0).  The transcription must stay op-for-op:
# the parity oracle compares the f64 numpy run against the real
# PongSimEnv bit-for-bit (tests/test_device_env.py).
# ---------------------------------------------------------------------------

def _tick(s: PongState, move, xp, f):
    """One raw emulator frame (pong_sim.PongSimEnv._tick); ``f`` is the
    physics scalar type (np.float32 / np.float64)."""
    py = xp.clip(s.player_y + move, f(5.0), f(79.0))
    err = s.ball_y - s.enemy_y
    ey = xp.clip(s.enemy_y + xp.clip(err, f(-ENEMY_SPEED), f(ENEMY_SPEED)),
                 f(5.0), f(79.0))
    bx = s.ball_x + s.ball_vx
    by = s.ball_y + s.ball_vy
    bvy = s.ball_vy
    lo = by < f(1.0)
    hi = by > f(83.0)
    by = xp.where(lo, f(2.0) - by, xp.where(hi, f(166.0) - by, by))
    bvy = xp.where(lo | hi, -bvy, bvy)
    bvx = s.ball_vx
    # paddle collisions: conditions from PRE-collision bvx/bx (the
    # host's if/elif — exclusive because they need opposite bvx signs)
    hitp = (bvx > 0) & (bx >= f(76.0)) & (xp.abs(by - py) <= f(6.0))
    hite = (~hitp) & (bvx < 0) & (bx <= f(6.0)) \
        & (xp.abs(by - ey) <= f(6.0))
    english_p = xp.clip(bvy + (f(0.5) * (by - py)) / f(5.0),
                        f(-2.0), f(2.0))
    english_e = xp.clip(bvy + (f(0.5) * (by - ey)) / f(5.0),
                        f(-2.0), f(2.0))
    bvy = xp.where(hitp, english_p, xp.where(hite, english_e, bvy))
    bx = xp.where(hitp, f(76.0), xp.where(hite, f(6.0), bx))
    bvx = xp.where(hitp | hite, -bvx, bvx)
    # scoring (the host's two early-return ifs; exclusive by bx's sign)
    p_scores = bx < f(0.0)           # player point, serve direction -1
    e_scores = bx > f(84.0)          # enemy point, serve direction +1
    scored = p_scores | e_scores
    reward = xp.where(p_scores, f(1.0),
                      xp.where(e_scores, f(-1.0), f(0.0)))
    direction = xp.where(p_scores, f(-1.0), f(1.0))
    u = np.uint32
    new_by = counter_uniform(s.seed, s.rng_count + u(1), 20.0, 64.0,
                             xp, f)
    new_bvy = counter_uniform(s.seed, s.rng_count + u(2), -1.2, 1.2,
                              xp, f)
    bx = xp.where(scored, f(42.0), bx)
    by = xp.where(scored, new_by, by)
    bvx = xp.where(scored, f(BALL_SPEED_X) * direction, bvx)
    bvy = xp.where(scored, new_bvy, bvy)
    count = (s.rng_count + xp.where(scored, u(2), u(0))).astype(np.uint32)
    one = np.int32(1)
    zero = np.int32(0)
    score_p = s.score_player + xp.where(p_scores, one, zero)
    score_e = s.score_enemy + xp.where(e_scores, one, zero)
    return s._replace(player_y=py, enemy_y=ey, ball_x=bx, ball_y=by,
                      ball_vx=bvx, ball_vy=bvy, score_enemy=score_e,
                      score_player=score_p, rng_count=count), reward


def _row_band(center, half, value, ys, xp, f):
    """(N, 84) uint8 row band [round(c-half), round(c+half)) at
    ``value`` — the vspan slice of pong_sim._draw as a mask."""
    lo = xp.round(center - f(half))[:, None]
    hi = xp.round(center + f(half))[:, None]
    return ((ys >= lo) & (ys < hi)).astype(np.uint8) * np.uint8(value)


def _ball_overlay(ball_x, ball_y, ys, xp):
    br = ((ys >= xp.round(ball_y)[:, None] - 1)
          & (ys < xp.round(ball_y)[:, None] + 1)).astype(np.uint8)
    bc = ((ys >= xp.round(ball_x)[:, None] - 1)
          & (ys < xp.round(ball_x)[:, None] + 1)).astype(np.uint8)
    return br[:, :, None] * (bc * np.uint8(236))[:, None, :]


def _static_cols(xp):
    cols = xp.arange(84)
    ecol = ((cols >= 2) & (cols < 4)).astype(np.uint8)[None, :]
    pcol = ((cols >= 78) & (cols < 80)).astype(np.uint8)[None, :]
    return ecol, pcol


def _render(s: PongState, xp, f):
    """(N, 84, 84) uint8 frame == pong_sim._draw.  The host draws
    background (35), enemy (130), player (150), ball (236) in overwrite
    order; the values are increasing, so overwrite == pixelwise max and
    the frame is the max of four mask contributions."""
    ys = xp.arange(84).astype(f)[None, :]
    er = _row_band(s.enemy_y, 5.0, 130, ys, xp, f)
    pr = _row_band(s.player_y, 5.0, 150, ys, xp, f)
    ecol, pcol = _static_cols(xp)
    frame = xp.maximum(er[:, :, None] * ecol[:, None, :],
                       pr[:, :, None] * pcol[:, None, :])
    return xp.maximum(
        xp.maximum(frame, _ball_overlay(s.ball_x, s.ball_y, ys, xp)),
        np.uint8(35))


def _render_union(s2: PongState, s3: PongState, xp, f):
    """max(render(s2), render(s3)) in ONE pass — the action-repeat
    maxpool (pong_sim._step's np.maximum over the last two raw frames)
    computed as a render over unioned masks.  Exact because each frame
    is a pixelwise max of its contributions (see _render), so the max
    of two frames is the max over both frames' contributions."""
    ys = xp.arange(84).astype(f)[None, :]
    er = xp.maximum(_row_band(s2.enemy_y, 5.0, 130, ys, xp, f),
                    _row_band(s3.enemy_y, 5.0, 130, ys, xp, f))
    pr = xp.maximum(_row_band(s2.player_y, 5.0, 150, ys, xp, f),
                    _row_band(s3.player_y, 5.0, 150, ys, xp, f))
    ecol, pcol = _static_cols(xp)
    frame = xp.maximum(er[:, :, None] * ecol[:, None, :],
                       pr[:, :, None] * pcol[:, None, :])
    ball = xp.maximum(_ball_overlay(s2.ball_x, s2.ball_y, ys, xp),
                      _ball_overlay(s3.ball_x, s3.ball_y, ys, xp))
    return xp.maximum(xp.maximum(frame, ball), np.uint8(35))


def _reset_state(seed, count, n: int, hist: int, xp, f) -> PongState:
    """Fresh-episode state for all N envs (pong_sim._reset): centered
    paddles, serve direction from one draw, ball y/vy from two more.
    ``count`` is the per-env draw counter BEFORE the reset draws."""
    u = np.uint32
    direction = xp.where(
        counter_uniform(seed, count + u(1), 0.0, 1.0, xp, f) < f(0.5),
        f(1.0), f(-1.0))
    by = counter_uniform(seed, count + u(2), 20.0, 64.0, xp, f)
    bvy = counter_uniform(seed, count + u(3), -1.2, 1.2, xp, f)
    # distinct arrays per field: a shared zeros object would alias
    # donated buffers once this state rides a donated rollout carry
    zi = lambda: xp.zeros((n,), np.int32)
    s = PongState(
        player_y=xp.full((n,), f(42.0)), enemy_y=xp.full((n,), f(42.0)),
        ball_x=xp.full((n,), f(42.0)), ball_y=by,
        ball_vx=f(BALL_SPEED_X) * direction, ball_vy=bvy,
        score_enemy=zi(), score_player=zi(), episode_steps=zi(),
        rng_count=(count + u(3)).astype(np.uint32),
        seed=xp.asarray(seed, np.uint32),
        stack=None)
    # reset-frame fast path: both paddles sit at the centered 42.0 and
    # the ball at x=42.0, so the paddle contribution is one CONSTANT
    # (1, 84, 84) base shared by all envs and only the ball overlay is
    # per-env — the step pays one cheap pass here instead of a full
    # render (auto-reset computes this branch every tick for all envs).
    # Bit-equal to _render(s): same contributions, max is order-free.
    ys = xp.arange(84).astype(f)[None, :]
    center = xp.full((1,), f(42.0))
    er = _row_band(center, 5.0, 130, ys, xp, f)
    pr = _row_band(center, 5.0, 150, ys, xp, f)
    ecol, pcol = _static_cols(xp)
    base = xp.maximum(er[:, :, None] * ecol[:, None, :],
                      pr[:, :, None] * pcol[:, None, :])
    first = xp.maximum(
        xp.maximum(base, _ball_overlay(s.ball_x, s.ball_y, ys, xp)),
        np.uint8(35))
    # host _reset fills the whole stack with the first frame
    rep = xp.broadcast_to(first[:, None], (n, hist, 84, 84))
    return s._replace(stack=rep + np.uint8(0))


def make_device_pong(env_params, slot_seeds, xp=None,
                     dtype=np.float32) -> DeviceEnv:
    """Build the Pong ``DeviceEnv`` for the given env slot seeds.

    ``xp=jax.numpy`` (default) gives the device env; ``xp=numpy`` gives
    the bit-identical host oracle the parity drill runs against.
    ``dtype`` is the physics dtype: f32 in production (TPU-native), f64
    for the oracle leg that must match the f64 host ``PongSimEnv``.
    """
    if xp is None:
        import jax.numpy as jnp

        xp = jnp
    f = np.dtype(dtype).type
    n = len(slot_seeds)
    hist = int(env_params.state_cha)
    rep = int(env_params.action_repetition)
    early_stop = int(env_params.early_stop or 0)
    seeds = np.asarray(slot_seeds, np.uint32)

    def init():
        return _reset_state(xp.asarray(seeds),
                            xp.zeros((n,), np.uint32), n, hist, xp, f)

    def observe(state: PongState):
        return state.stack

    def step(state: PongState, actions):
        a = xp.asarray(actions)
        move = xp.where((a == 2) | (a == 4), f(-2.0),
                        xp.where((a == 3) | (a == 5), f(2.0), f(0.0)))
        reward = xp.zeros((n,), dtype)
        s = state
        states = []
        for _k in range(rep):
            s, r = _tick(s, move, xp, f)
            reward = reward + r
            states.append(s)
        if rep >= 2:
            frame = _render_union(states[rep - 2], states[rep - 1], xp, f)
        else:
            frame = _render(s, xp, f)
        true_stack = xp.concatenate([state.stack[:, 1:], frame[:, None]],
                                    axis=1)
        steps = s.episode_steps + np.int32(1)
        game_over = xp.maximum(s.score_enemy, s.score_player) >= WIN_SCORE
        if early_stop:
            truncated = steps >= early_stop
        else:
            truncated = xp.zeros((n,), bool)
        terminal = game_over | truncated
        score = xp.stack([s.score_enemy, s.score_player], axis=1)
        # auto-reset: the returned obs for terminal envs is the fresh
        # episode's first stack; the true terminal stack rides final_obs
        fresh = _reset_state(s.seed, s.rng_count, n, hist, xp, f)

        def sel(a_new, a_old):
            t = terminal
            extra = a_old.ndim - t.ndim
            if extra:
                t = t.reshape(t.shape + (1,) * extra)
            return xp.where(t, a_new, a_old)

        s = s._replace(episode_steps=steps, stack=true_stack)
        nxt = PongState(*(sel(f_new, f_old)
                          for f_new, f_old in zip(fresh, s)))
        nxt = nxt._replace(seed=state.seed)  # constant; keep dtype exact
        return nxt, StepOut(obs=nxt.stack, final_obs=true_stack,
                            reward=reward.astype(np.float32),
                            terminal=terminal, truncated=truncated,
                            score=score)

    return DeviceEnv(num_envs=n, state_shape=(hist, 84, 84),
                     num_actions=6, norm_val=255.0,
                     init=init, step=step, observe=observe)


# ---------------------------------------------------------------------------
# factory surface
# ---------------------------------------------------------------------------

# device env families (family name -> builder) and which env_type each
# family implements — the family is a device RE-IMPLEMENTATION of a
# host env_type, so the two must always agree (a Pong fleet behind a
# cartpole learner config would train on the wrong environment)
DEVICE_ENV_FAMILIES: Dict[str, Callable] = {
    "pong": make_device_pong,
}
_ENV_TYPE_FAMILY: Dict[str, str] = {
    "pong-sim": "pong",
}


def resolve_device_env_family(env_params) -> str | None:
    """The device family for this env config, or None when the
    env_type has no device implementation.  An explicit
    ``device_env_family`` must NAME the env_type's own family — it
    pins/documents the choice (and will disambiguate once an env_type
    has several implementations); it can never substitute a different
    game than the host config runs."""
    fam = _ENV_TYPE_FAMILY.get(env_params.env_type)
    explicit = getattr(env_params, "device_env_family", "auto") or "auto"
    if explicit == "auto":
        return fam
    if explicit != fam:
        raise ValueError(
            f"device_env_family={explicit!r} does not implement "
            f"env_type={env_params.env_type!r} (its device family is "
            f"{fam!r}; families: {sorted(DEVICE_ENV_FAMILIES)})")
    return fam


def device_env_supported(env_params) -> bool:
    """One gate shared by factory.resolve_actor_backend and the
    builders: does this env config have a device implementation?"""
    return resolve_device_env_family(env_params) is not None


def build_device_env(env_params, process_ind: int, num_envs: int,
                     xp=None, dtype=np.float32) -> DeviceEnv:
    """The device env for one actor slot, seeded on the fleet slot
    contract (env j of actor i takes slot ``seed + i*N + j`` — the same
    stream positions factory.build_env_vector hands the host
    backends)."""
    fam = resolve_device_env_family(env_params)
    if fam is None:
        raise ValueError(
            f"no device env implementation for env_type="
            f"{env_params.env_type!r} (families: "
            f"{sorted(DEVICE_ENV_FAMILIES)})")
    return DEVICE_ENV_FAMILIES[fam](
        env_params,
        [env_params.seed + process_ind * num_envs + j
         for j in range(num_envs)],
        xp=xp, dtype=dtype)


# ---------------------------------------------------------------------------
# host-facing wrapper (a VectorEnv drop-in)
# ---------------------------------------------------------------------------

class DevicePongVectorEnv:
    """Drive the jitted device Pong from a host loop — the
    ``VectorEnv`` surface (reset/step with ``final_obs``/``truncated``
    infos) over the device state, so inline/pipelined actors and the
    parity drill can run the device env without the fused engine."""

    def __init__(self, env_params, process_ind: int, num_envs: int):
        import jax

        self.params = env_params
        self.num_envs = num_envs
        self.norm_val = 255.0
        self.training = True
        self._env = build_device_env(env_params, process_ind, num_envs)
        self._step = jax.jit(self._env.step)
        self._state = None

    def train(self) -> None:
        self.training = True

    def eval(self) -> None:
        self.training = False

    @property
    def state_shape(self) -> Tuple[int, ...]:
        return self._env.state_shape

    @property
    def action_space(self) -> DiscreteSpace:
        return DiscreteSpace(self._env.num_actions)

    def reset(self) -> np.ndarray:
        self._state = self._env.init()
        return np.asarray(self._env.observe(self._state))

    def step(self, actions) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                     List[Dict[str, Any]]]:
        acts = np.ascontiguousarray(np.asarray(actions, dtype=np.int32))
        assert acts.shape == (self.num_envs,)
        self._state, out = self._step(self._state, acts)
        obs = np.asarray(out.obs)
        reward = np.asarray(out.reward)
        terminal = np.asarray(out.terminal)
        truncated = np.asarray(out.truncated)
        score = np.asarray(out.score)
        final = None
        infos: List[Dict[str, Any]] = []
        for j in range(self.num_envs):
            info: Dict[str, Any] = {
                "score": tuple(int(v) for v in score[j])}
            if terminal[j]:
                if final is None:
                    final = np.asarray(out.final_obs)
                info["final_obs"] = final[j]
                if truncated[j]:
                    info["truncated"] = True
            infos.append(info)
        return obs, reward, terminal, infos
