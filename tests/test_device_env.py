"""Device env fleet (ISSUE 7): parity oracle drill + fused rollout.

The parity chain has three legs, each bit-exact:

1. **f64 numpy kernel == the real host ``PongSimEnv``** over full
   episodes (auto-reset, truncation, ``final_obs``) with the host
   class's RNG replaced by the device env's counter stream
   (``CounterRng``) — proves the PORT is op-for-op faithful to the
   production host env, including the preprocessing pipeline.
2. **jitted f32 device env == f32 numpy kernel** over full episodes —
   proves XLA executes the same arithmetic the oracle runs (no fusion
   / FMA / layout surprises), auto-resets included.
3. **f32 device env == the real f64 ``PongSimEnv``** from an identical
   mid-court state over a horizon with binary-representable velocities
   — a direct device-vs-host bridge with no RNG and no dtype drift
   (the technique tests/test_native_pong.py uses for the C++ stepper).

The fused rollout engine is pinned against the HOST reference loop:
``build_packed_act`` + ``NStepAssembler`` over ``DevicePongVectorEnv``
must produce the identical transition stream (states, rewards,
gamma_n, terminals) the one-dispatch scan emits.
"""

from __future__ import annotations

import numpy as np
import pytest

from pytorch_distributed_tpu.config import build_options
from pytorch_distributed_tpu.envs.device_env import (
    CounterRng, DevicePongVectorEnv, build_device_env,
    device_env_supported, make_device_pong,
)
from pytorch_distributed_tpu.envs.pong_sim import PongSimEnv


def _env_params(**kw):
    opt = build_options(4)
    for k, v in kw.items():
        setattr(opt.env_params, k, v)
    return opt.env_params


def _patched_hosts(ep, slots):
    """Real PongSimEnv instances replaying the device counter stream.
    The shim is installed post-__init__ (the constructor's throwaway
    ``_reset_ball`` draws are not part of the device stream), so the
    first ``reset()`` consumes counters 1..3 exactly like the device
    ``init``."""
    hosts = []
    for s in slots:
        e = PongSimEnv(ep, process_ind=s - ep.seed)
        e.rng = CounterRng(s)
        hosts.append(e)
    return hosts


class TestParityOracle:
    def test_f64_oracle_matches_host_pongsim_full_episodes(self):
        """Leg 1: numpy f64 kernel == the real host class, through
        auto-reset boundaries (early_stop=40 forces several)."""
        ep = _env_params(early_stop=40)
        slots = [ep.seed + j for j in range(3)]
        oracle = make_device_pong(ep, slots, xp=np, dtype=np.float64)
        st = oracle.init()
        hosts = _patched_hosts(ep, slots)
        obs_h = np.stack([e.reset() for e in hosts])
        np.testing.assert_array_equal(np.asarray(st.stack), obs_h)
        rng = np.random.default_rng(0)
        resets = 0
        for _t in range(100):
            acts = rng.integers(0, 6, size=3)
            st, out = oracle.step(st, acts)
            for j, e in enumerate(hosts):
                o, r, term, info = e.step(int(acts[j]))
                assert float(out.reward[j]) == r
                assert bool(out.terminal[j]) == bool(term)
                assert bool(out.truncated[j]) == bool(
                    info.get("truncated", False))
                if term:
                    resets += 1
                    # true terminal obs preserved, then auto-reset
                    np.testing.assert_array_equal(
                        np.asarray(out.final_obs[j]), o)
                    o = e.reset()
                np.testing.assert_array_equal(np.asarray(out.obs[j]), o)
        assert resets >= 3, "horizon must cross episode boundaries"

    def test_device_f32_matches_numpy_oracle_full_episodes(self):
        """Leg 2: jitted XLA f32 == numpy f32, every StepOut field."""
        import jax

        ep = _env_params(early_stop=30)
        dev = build_device_env(ep, 0, 4)
        orc = make_device_pong(ep, [ep.seed + j for j in range(4)],
                               xp=np, dtype=np.float32)
        jstep = jax.jit(dev.step)
        sd, so = dev.init(), orc.init()
        for fd, fo in zip(sd, so):
            np.testing.assert_array_equal(np.asarray(fd), fo)
        rng = np.random.default_rng(1)
        for t in range(80):
            acts = rng.integers(0, 6, size=4).astype(np.int32)
            sd, od = jstep(sd, acts)
            so, oo = orc.step(so, acts)
            for name, a, b in zip(od._fields, od, oo):
                assert np.array_equal(np.asarray(a), b), (t, name)

    def test_device_f32_matches_real_pongsim_representable_horizon(self):
        """Leg 3: device vs the UNMODIFIED f64 host env from one
        mid-court state.  Velocities are binary fractions (1.5, 0.25)
        and the enemy paddle starts locked onto the ball, so every
        f32 and f64 trajectory value is exact until the first paddle
        contact — frames must match bit-for-bit."""
        import jax

        ep = _env_params()
        host = PongSimEnv(ep, process_ind=0)
        host.reset()
        host.player_y, host.enemy_y = 20.0, 40.0
        host.ball_x, host.ball_y = 42.0, 40.0
        host.ball_vx, host.ball_vy = 1.5, 0.25
        host._score = [0, 0]

        dev = build_device_env(ep, 0, 1)
        st = dev.init()
        st = st._replace(
            player_y=np.asarray([20.0], np.float32),
            enemy_y=np.asarray([40.0], np.float32),
            ball_x=np.asarray([42.0], np.float32),
            ball_y=np.asarray([40.0], np.float32),
            ball_vx=np.asarray([1.5], np.float32),
            ball_vy=np.asarray([0.25], np.float32))
        jstep = jax.jit(dev.step)
        for t, a in enumerate([0, 2, 3, 0, 1]):
            obs_h, r_h, term_h, _ = host.step(a)
            st, out = jstep(st, np.asarray([a], np.int32))
            assert r_h == 0.0 and float(out.reward[0]) == 0.0
            assert not term_h and not bool(out.terminal[0])
            np.testing.assert_array_equal(np.asarray(out.obs[0, -1]),
                                          obs_h[-1])

    def test_game_over_scores_resets_and_reports(self):
        """Scoring + game end via state surgery: player at match point,
        ball about to cross the enemy goal line — both the oracle and
        the device must score, flag the terminal, report (0, 21), and
        auto-reset with the true final stack in final_obs."""
        import jax

        ep = _env_params()
        dev = build_device_env(ep, 0, 2)
        orc = make_device_pong(ep, [ep.seed, ep.seed + 1], xp=np,
                               dtype=np.float32)
        sd, so = dev.init(), orc.init()

        def surgery(s):
            return s._replace(
                score_player=np.asarray([20, 0], np.int32),
                ball_x=np.asarray([2.0, 42.0], np.float32),
                ball_y=np.asarray([70.0, 40.0], np.float32),
                ball_vx=np.asarray([-1.4, 1.4], np.float32),
                ball_vy=np.asarray([0.0, 0.0], np.float32),
                enemy_y=np.asarray([10.0, 40.0], np.float32))

        sd, so = surgery(sd), surgery(so)
        sd, od = jax.jit(dev.step)(sd, np.zeros(2, np.int32))
        so, oo = orc.step(so, np.zeros(2, np.int32))
        for name, a, b in zip(od._fields, od, oo):
            assert np.array_equal(np.asarray(a), b), name
        assert float(od.reward[0]) == 1.0 and float(od.reward[1]) == 0.0
        assert bool(od.terminal[0]) and not bool(od.terminal[1])
        assert not bool(od.truncated[0])
        assert tuple(np.asarray(od.score[0])) == (0, 21)
        # env 0 auto-reset: returned obs is a fresh stack (all frames
        # equal), final_obs keeps the terminal stack
        obs0 = np.asarray(od.obs[0])
        for k in range(1, obs0.shape[0]):
            np.testing.assert_array_equal(obs0[0], obs0[k])
        assert not np.array_equal(np.asarray(od.final_obs[0]), obs0)
        # scores reset on device state too
        assert int(np.asarray(sd.score_player)[0]) == 0

    def test_wrapper_vector_env_contract(self):
        """DevicePongVectorEnv mirrors envs/vector.py: shapes, spaces,
        final_obs/truncated infos, auto-reset."""
        ep = _env_params(early_stop=5)
        env = DevicePongVectorEnv(ep, process_ind=0, num_envs=3)
        obs = env.reset()
        assert obs.shape == (3, 4, 84, 84) and obs.dtype == np.uint8
        assert env.state_shape == (4, 84, 84)
        assert env.action_space.n == 6 and env.norm_val == 255.0
        for _ in range(5):
            obs, rew, term, infos = env.step(np.zeros(3, np.int64))
        assert term.all()
        for j in range(3):
            assert infos[j].get("truncated") is True
            assert "final_obs" in infos[j]
            assert not np.array_equal(infos[j]["final_obs"], obs[j])
        _, _, term, _ = env.step(np.zeros(3, np.int64))
        assert not term.any()


class TestSlotSeedContract:
    """ISSUE 7 satellite: env j of actor i takes seed slot i*N + j on
    EVERY backend, so backend choice never changes the seed stream."""

    def test_python_backend_slots(self):
        from pytorch_distributed_tpu.factory import build_env_vector

        opt = build_options(4)
        opt.env_params.native_env = False
        v = build_env_vector(opt, process_ind=2, num_envs=3)
        assert [e.seed for e in v.envs] == [
            opt.env_params.seed + 2 * 3 + j for j in range(3)]

    def test_device_backend_slots(self):
        ep = _env_params()
        env = build_device_env(ep, process_ind=2, num_envs=3)
        st = env.init()
        np.testing.assert_array_equal(
            np.asarray(st.seed),
            np.asarray([ep.seed + 2 * 3 + j for j in range(3)],
                       np.uint32))

    def test_slot_identity_across_split_points(self):
        """Slot (i*N + j) identifies the stream, not (i, j): actor 1
        of width 2 must reproduce envs 2..3 of one width-4 actor —
        checked per backend against its own RNG scheme."""
        ep = _env_params()
        a = build_device_env(ep, process_ind=1, num_envs=2)
        b = build_device_env(ep, process_ind=0, num_envs=4)
        oa = np.asarray(a.init().stack)
        ob = np.asarray(b.init().stack)
        np.testing.assert_array_equal(oa, ob[2:4])
        try:
            from pytorch_distributed_tpu.envs.native_pong import (
                NativePongVectorEnv, get_lib,
            )

            get_lib()
        except Exception:  # noqa: BLE001 - no toolchain
            return
        na = NativePongVectorEnv(ep, 1, 2)
        nb = NativePongVectorEnv(ep, 0, 4)
        np.testing.assert_array_equal(na.reset(), nb.reset()[2:4])

    def test_resolve_backend_gates(self):
        import warnings

        from pytorch_distributed_tpu.factory import resolve_actor_backend

        opt = build_options(4, actor_backend="device")
        assert resolve_actor_backend(opt) == "device"
        assert device_env_supported(opt.env_params)
        # an explicit family must name the env_type's OWN device
        # implementation — substituting a different game raises
        opt.env_params.device_env_family = "pong"
        assert device_env_supported(opt.env_params)
        mismatched = build_options(3).env_params  # cartpole row
        mismatched.device_env_family = "pong"
        with pytest.raises(ValueError, match="does not implement"):
            device_env_supported(mismatched)
        # unsupported env family downgrades loudly
        opt2 = build_options(1, actor_backend="device")
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            assert resolve_actor_backend(opt2) == "pipelined"
        assert any("device env" in str(x.message) for x in w)
        # non-dqn family downgrades loudly
        opt3 = build_options(2, actor_backend="device")
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            assert resolve_actor_backend(opt3) == "pipelined"
        assert any("dqn" in str(x.message) for x in w)


# ---------------------------------------------------------------------------
# the fused rollout engine
# ---------------------------------------------------------------------------

def _linear_policy(state_shape, num_actions=6, seed=0):
    import jax.numpy as jnp

    dim = int(np.prod(state_shape))
    w = jnp.asarray(np.random.default_rng(seed).normal(
        size=(dim, num_actions)).astype(np.float32) * 0.05)

    def apply_fn(params, obs):
        x = obs.reshape((obs.shape[0], -1)).astype(jnp.float32) / 255.0
        return x @ params

    return apply_fn, w


class TestFusedRollout:
    N, NSTEP, GAMMA, K, DISPATCHES = 3, 3, 0.99, 5, 5

    @pytest.fixture(scope="class")
    def run(self):
        """One engine run + one host-reference run over the same env,
        policy and key streams; class-scoped so every assertion shares
        the compiles."""
        import jax
        import jax.numpy as jnp

        from pytorch_distributed_tpu.models.policies import (
            apex_epsilons, build_fused_rollout, build_packed_act,
            init_rollout_carry,
        )
        from pytorch_distributed_tpu.ops.nstep import NStepAssembler
        from pytorch_distributed_tpu.utils.rngs import process_key

        ep = _env_params(early_stop=20)
        N, NSTEP, GAMMA, K = self.N, self.NSTEP, self.GAMMA, self.K
        env = build_device_env(ep, 0, N)
        apply_fn, w = _linear_policy(env.state_shape)
        base_key = process_key(100, "actor", 0)
        eps = jnp.asarray(apex_epsilons(0, 2, N, 0.4, 7.0))

        roll = build_fused_rollout(apply_fn, env, nstep=NSTEP,
                                   gamma=GAMMA, rollout_ticks=K,
                                   emit="chunk")
        carry = init_rollout_carry(env, NSTEP)
        chunks = []
        for d in range(self.DISPATCHES):
            carry, chunk = roll(w, carry, base_key, jnp.int32(d * K),
                                eps)
            chunks.append(jax.device_get(chunk._asdict()))

        # host reference: packed act + host assembler over the wrapper
        wrap = DevicePongVectorEnv(ep, 0, N)
        act = build_packed_act(apply_fn)
        asms = [NStepAssembler(NSTEP, GAMMA) for _ in range(N)]
        obs = wrap.reset()
        host = [[] for _ in range(N)]
        qmax_ref = []
        for t in range(self.DISPATCHES * K):
            packed = np.asarray(act(w, obs, base_key, t, eps))
            qmax_ref.append(packed[2].copy())
            actions = packed[0].astype(np.int64)
            nobs, rew, term, infos = wrap.step(actions)
            for j in range(N):
                true_next = infos[j].get("final_obs", nobs[j])
                for tr in asms[j].feed(
                        obs[j], actions[j], float(rew[j]), true_next,
                        bool(term[j]),
                        truncated=bool(infos[j].get("truncated",
                                                    False))):
                    host[j].append(tr)
            obs = nobs
        return dict(chunks=chunks, host=host, qmax_ref=qmax_ref)

    def _fused_rows(self, chunks):
        """Valid emissions in (tick, env) order with their global
        emission tick."""
        rows = []
        for d, ch in enumerate(chunks):
            for k in range(self.K):
                for j in range(self.N):
                    if ch["valid"][k][j]:
                        rows.append((d * self.K + k, j,
                                     {f: np.asarray(ch[f][k][j])
                                      for f in ch}))
        return rows

    def test_warmup_ticks_are_invalid_then_all_valid(self, run):
        ch0 = run["chunks"][0]
        valid = np.asarray(ch0["valid"])
        assert not valid[:self.NSTEP].any()
        assert valid[self.NSTEP:].all()
        for ch in run["chunks"][1:]:
            assert np.asarray(ch["valid"]).all()

    def test_transition_stream_matches_host_assembler(self, run):
        rows = self._fused_rows(run["chunks"])
        per_env = [[] for _ in range(self.N)]
        for _te, j, row in rows:
            per_env[j].append(row)
        compared = 0
        for j in range(self.N):
            m = min(len(run["host"][j]), len(per_env[j]))
            assert m >= 15  # crosses several truncation boundaries
            for i in range(m):
                h, f = run["host"][j][i], per_env[j][i]
                np.testing.assert_array_equal(h.state0, f["state0"])
                np.testing.assert_array_equal(h.state1, f["state1"])
                assert int(h.action) == int(f["action"])
                assert h.reward == f["reward"]
                assert h.gamma_n == f["gamma_n"]
                assert h.terminal1 == f["terminal1"]
                compared += 1
        assert compared >= 45

    def test_bootstrap_q_column_is_the_next_forward(self, run):
        """Steady-state windows close at te-1 and bootstrap from the
        forward at te — the emission tick itself (the host pending
        queue's exact semantics)."""
        checked = 0
        for te, j, row in self._fused_rows(run["chunks"]):
            steady = (row["gamma_n"] == np.float32(
                self.GAMMA ** self.NSTEP)) and row["terminal1"] == 0 \
                and bool(row["prio_ok"])
            if steady:
                assert row["q_boot"] == run["qmax_ref"][te][j]
                checked += 1
        assert checked >= 10

    def test_truncated_windows_marked_no_priority(self, run):
        rows = self._fused_rows(run["chunks"])
        trunc_rows = [r for _, _, r in rows if not r["prio_ok"]]
        # early_stop=20 with 25 ticks -> one boundary, nstep windows
        # per env close there
        assert len(trunc_rows) >= self.N
        for r in trunc_rows:
            assert r["terminal1"] == 0.0  # truncation still bootstraps

    def test_rollout_priorities_formula(self, run):
        from pytorch_distributed_tpu.models.policies import (
            rollout_priorities,
        )

        rows = [r for _, _, r in self._fused_rows(run["chunks"])]
        flat = {f: np.asarray([r[f] for r in rows])
                for f in ("reward", "gamma_n", "terminal1", "q_boot",
                          "q_sel", "prio_ok")}
        pr = rollout_priorities(flat, True)
        assert pr.shape == (len(rows),)
        for i, r in enumerate(rows):
            if not r["prio_ok"]:
                assert pr[i] is None
            else:
                want = abs(float(r["reward"])
                           + float(r["gamma_n"])
                           * (1.0 - float(r["terminal1"]))
                           * float(r["q_boot"]) - float(r["q_sel"]))
                assert pr[i] == pytest.approx(want)
        assert rollout_priorities(flat, False) is None

    def test_replay_emit_matches_chunk_emit(self, run):
        """emit="replay" scatters the SAME rows straight into a device
        ring (zero host round-trip) — contents must equal the chunk
        emissions row for row."""
        import jax
        import jax.numpy as jnp

        from pytorch_distributed_tpu.memory.device_replay import (
            DeviceReplay,
        )
        from pytorch_distributed_tpu.models.policies import (
            apex_epsilons, build_fused_rollout, init_rollout_carry,
        )
        from pytorch_distributed_tpu.utils.rngs import process_key

        ep = _env_params(early_stop=20)
        env = build_device_env(ep, 0, self.N)
        apply_fn, w = _linear_policy(env.state_shape)
        roll = build_fused_rollout(apply_fn, env, nstep=self.NSTEP,
                                   gamma=self.GAMMA,
                                   rollout_ticks=self.K, emit="replay")
        ring = DeviceReplay(capacity=256, state_shape=env.state_shape,
                            state_dtype=np.uint8)
        carry = init_rollout_carry(env, self.NSTEP)
        rs = ring.state
        base_key = process_key(100, "actor", 0)
        eps = jnp.asarray(apex_epsilons(0, 2, self.N, 0.4, 7.0))
        fed = 0
        for d in range(self.DISPATCHES):
            carry, rs, stats = roll(w, carry, rs, base_key,
                                    jnp.int32(d * self.K), eps)
            fed += int(stats.fed)
        rows = [r for _, _, r in self._fused_rows(run["chunks"])]
        assert fed == len(rows)
        rs_h = jax.device_get(rs)
        assert int(rs_h.fill) == fed
        # the observation columns are read through the ring's codec
        rs_h = rs_h._replace(state0=rs_h.codec.unpack(rs_h.state0),
                             state1=rs_h.codec.unpack(rs_h.state1))
        for i, row in enumerate(rows):
            for f in ("state0", "action", "reward", "gamma_n",
                      "state1", "terminal1"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(rs_h, f)[i]), row[f],
                    err_msg=f"ring row {i} field {f}")


class TestDeviceActorDriver:
    def test_bounded_device_run_feeds_counts_and_exports_perf(self,
                                                              tmp_path,
                                                              monkeypatch):
        """The actor_backend=device driver end to end in-process: real
        dqn-cnn model, device Pong fleet, recording sink.  Checks the
        transition stream arrives, the clock advances K*N per
        dispatch, and the perf plane captured the rollout program
        (frames counter + per-frame FLOPs + retrace registration)."""
        monkeypatch.setenv("TPU_APEX_PERF", "1")
        from pytorch_distributed_tpu.agents.actor import (
            bounded_actor_run,
        )
        from pytorch_distributed_tpu.utils import perf

        perf.reset()
        opt = build_options(
            4, root_dir=str(tmp_path), refs="dev_drv", num_actors=1,
            num_envs_per_actor=4, actor_backend="device",
            visualize=False, actor_freq=10 ** 9,
            actor_sync_freq=10 ** 9)
        opt.env_params.device_rollout_ticks = 2
        dispatches = 4
        res = bounded_actor_run(opt, ticks=dispatches)
        stream = res["stream"]
        # warmup holds back nstep emissions per env
        expected = (dispatches * 2 - opt.agent_params.nstep) * 4
        assert len(stream) == expected
        t0, pr0 = stream[0]
        assert t0.state0.shape == (4, 84, 84)
        assert t0.state0.dtype == np.uint8
        assert pr0 is None  # uniform replay: no actor-side priorities
        h = res["harness"]
        assert h.env is None  # no host env objects in a device actor
        assert h.perf._frames == dispatches * 2 * 4
        assert h.perf.flops_per_frame and h.perf.flops_per_frame > 0
        assert "device_rollout" in h.perf.retraces._names
        perf.reset()


class TestFleetStatusActorsBlock:
    def test_health_snapshot_reports_per_actor_rate_and_backend(self,
                                                                tmp_path):
        """ISSUE 7 satellite: the gateway STATUS payload carries a
        per-LOCAL-actor block — env frames/s derived from the progress
        board's tick marks over the provider's rate window, plus the
        resolved schedule — and it is what fleet_top's --json prints."""
        import json as _json
        import time as _time

        from pytorch_distributed_tpu.fleet import FleetTopology

        opt = build_options(
            4, num_actors=2, num_envs_per_actor=8, seed=7,
            root_dir=str(tmp_path), actor_backend="device",
            visualize=False)
        topo = FleetTopology(opt, local_actors=2, port=0)
        try:
            h0 = topo._health_snapshot()  # anchors the rate window
            # two dispatches' worth of ticks on actor-0, one on actor-1
            topo.progress_board.note_start("actor-0")
            topo.progress_board.note_start("actor-1")
            topo.progress_board.bump("actor-0", n=4)
            topo.progress_board.bump("actor-1", n=2)
            _time.sleep(0.6)  # provider ignores sub-0.5s windows
            h1 = topo._health_snapshot()
            actors = h1["actors"]
            assert set(actors) == {"0", "1"}
            for slot in ("0", "1"):
                assert actors[slot]["backend"] == "device"
            # rate = marks * num_envs / window; exact dt is wall-clock,
            # so assert proportions and positivity instead
            assert actors["0"]["env_frames_per_sec"] > 0
            assert actors["0"]["env_frames_per_sec"] > \
                actors["1"]["env_frames_per_sec"]
            _json.dumps(h1)  # the --json path must serialize
        finally:
            topo.gateway.close()
