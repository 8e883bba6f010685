"""Crash-consistent checkpoint epochs (utils/checkpoint.py): atomic
manifest commit, torn/digest-mismatch rejection, retention GC, geometry
validation (CheckpointMismatch), cross-family snapshot interchange, and
the kill-resume drills — SIGKILL at exact write points via the
``CKPT_FAULTS`` schedule (utils/faults.py ``kill@FRAME``), then assert a
subsequent resume always finds a complete, digest-valid epoch with
mutually consistent counters.  The slow tier runs the same drill on the
full training topology, plus the SIGTERM-preemption path
(runtime.py)."""

import os
import re
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from pytorch_distributed_tpu.memory.feeder import QueueOwner
from pytorch_distributed_tpu.memory.prioritized import PrioritizedReplay
from pytorch_distributed_tpu.memory.sequence_replay import (
    Segment, SequenceReplay,
)
from pytorch_distributed_tpu.memory.shared_replay import SharedReplay
from pytorch_distributed_tpu.utils import checkpoint as ckpt
from pytorch_distributed_tpu.utils.experience import Transition

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)
KILL_CHILD = os.path.join(_HERE, "_ckpt_kill_child.py")
TOPO_CHILD = os.path.join(_HERE, "_kill_resume_child.py")


def geom(capacity, shape=(4,), dtype=np.uint8):
    return dict(capacity=capacity, state_shape=shape, action_shape=(),
                state_dtype=dtype, action_dtype=np.int32)


def fill(mem, n, seed=0, priorities=False):
    rng = np.random.default_rng(seed)
    for i in range(n):
        mem.feed(Transition(
            state0=rng.integers(0, 255, (4,)).astype(np.uint8),
            action=np.int32(i % 3), reward=np.float32(i),
            gamma_n=np.float32(0.99),
            state1=rng.integers(0, 255, (4,)).astype(np.uint8),
            terminal1=np.float32(i % 7 == 0)),
            float(i % 5) if priorities else None)


def tiny_state(step=0):
    import jax.numpy as jnp

    return {"w": jnp.full((16,), float(step)), "step": jnp.int32(step)}


def _child_env(extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("CKPT_FAULTS", None)
    # children need no virtual multi-device mesh; a 1-device CPU backend
    # starts faster
    env["XLA_FLAGS"] = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        env.get("XLA_FLAGS", "")).strip()
    if extra:
        env.update(extra)
    return env


def run_child(script, args, extra_env=None, timeout=240):
    p = subprocess.run(
        [sys.executable, script, *map(str, args)], env=_child_env(extra_env),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, timeout=timeout)
    return p.returncode, p.stdout.decode()


# ---------------------------------------------------------------------------
# epoch subsystem units
# ---------------------------------------------------------------------------

class TestEpochSubsystem:
    def test_save_resolve_roundtrip(self, tmp_path):
        import jax.numpy as jnp

        mn = str(tmp_path / "m")
        mem = SharedReplay(**geom(32))
        fill(mem, 20)
        ed = ckpt.save_epoch(mn, state=tiny_state(7), memory=mem,
                             extras={"learner_step": 7, "actor_step": 21,
                                     "best_eval_reward": 1.5})
        assert os.path.exists(os.path.join(ed, ckpt.MANIFEST))
        info = ckpt.resolve_epoch(mn)
        assert (info.epoch, info.learner_step) == (0, 7)
        assert info.has_state and info.has_replay
        assert info.extras["actor_step"] == 21
        assert info.extras["best_eval_reward"] == 1.5
        st = ckpt.load_epoch_state(info, tiny_state(0))
        assert int(st["step"]) == 7
        assert float(np.asarray(st["w"])[0]) == 7.0
        mem2 = SharedReplay(**geom(32))
        assert ckpt.load_epoch_replay(info, mem2) == 20
        assert mem2.size == 20
        np.testing.assert_array_equal(
            np.sort(mem2._np_reward[:20]), np.arange(20, dtype=np.float32))
        # jnp only used via tiny_state; silence linters
        assert jnp is not None

    def test_torn_epoch_skipped_and_cleared(self, tmp_path):
        mn = str(tmp_path / "m")
        for s in (5, 10):
            ckpt.save_epoch(mn, state=tiny_state(s),
                            extras={"learner_step": s})
        root = ckpt.ckpt_root(mn)
        # tear the newest: a crash between the artifact writes and the
        # manifest commit leaves exactly this
        os.remove(os.path.join(root, "epoch_1", ckpt.MANIFEST))
        info = ckpt.resolve_epoch(mn)
        assert (info.epoch, info.learner_step) == (0, 5)
        rep = ckpt.fsck(root)
        assert rep["violations"] == []  # torn-uncommitted is debris, not a lie
        assert rep["newest_complete"] == 0
        # the next save reuses the torn slot and the numbering continues
        ckpt.save_epoch(mn, state=tiny_state(15),
                        extras={"learner_step": 15})
        info2 = ckpt.resolve_epoch(mn)
        assert (info2.epoch, info2.learner_step) == (1, 15)

    def test_digest_mismatch_rejected(self, tmp_path):
        mn = str(tmp_path / "m")
        mem = SharedReplay(**geom(32))
        fill(mem, 10)
        for s in (5, 10):
            ckpt.save_epoch(mn, state=tiny_state(s), memory=mem,
                            extras={"learner_step": s})
        root = ckpt.ckpt_root(mn)
        with open(os.path.join(root, "epoch_1", "replay.npz"), "r+b") as f:
            f.seek(40)
            f.write(b"\xff\xff\xff")
        info = ckpt.resolve_epoch(mn)
        assert (info.epoch, info.learner_step) == (0, 5)
        rep = ckpt.fsck(root)
        assert any("digest mismatch" in v for v in rep["violations"])
        assert rep["newest_complete"] == 0

    def test_manifest_garbage_rejected(self, tmp_path):
        mn = str(tmp_path / "m")
        for s in (5, 10):
            ckpt.save_epoch(mn, state=tiny_state(s),
                            extras={"learner_step": s})
        root = ckpt.ckpt_root(mn)
        with open(os.path.join(root, "epoch_1", ckpt.MANIFEST), "w") as f:
            f.write("{not json")
        assert ckpt.resolve_epoch(mn).epoch == 0
        assert any("unreadable" in v for v in ckpt.fsck(root)["violations"])

    def test_extras_step_inconsistency_is_a_violation(self, tmp_path):
        import json

        mn = str(tmp_path / "m")
        ckpt.save_epoch(mn, state=tiny_state(5), extras={"learner_step": 5})
        ed = os.path.join(ckpt.ckpt_root(mn), "epoch_0")
        with open(os.path.join(ed, ckpt.MANIFEST)) as f:
            man = json.load(f)
        man["learner_step"] = 999  # counters no longer one triple
        # re-digest extras stays valid; only the cross-check must trip
        with open(os.path.join(ed, ckpt.MANIFEST), "w") as f:
            json.dump(man, f)
        status, bad = ckpt.verify_epoch(ed)
        assert status == "corrupt"
        assert any("learner_step" in v for v in bad)

    def test_retention_gc(self, tmp_path):
        mn = str(tmp_path / "m")
        for s in range(5):
            ckpt.save_epoch(mn, state=tiny_state(s),
                            extras={"learner_step": s}, retain=2)
        root = ckpt.ckpt_root(mn)
        kept = sorted(os.listdir(root))
        assert kept == ["epoch_3", "epoch_4"]
        assert ckpt.resolve_epoch(mn).learner_step == 4

    def test_resolve_empty_and_missing(self, tmp_path):
        assert ckpt.resolve_epoch(str(tmp_path / "none")) is None
        os.makedirs(str(tmp_path / "e_ckpt"))
        assert ckpt.resolve_epoch(str(tmp_path / "e")) is None
        rep = ckpt.fsck(str(tmp_path / "missing_ckpt"))
        assert rep["violations"]  # no such directory


class TestLegacySingleSnapshot:
    def test_save_is_publish_by_rename_not_overwrite(self, tmp_path):
        import jax.numpy as jnp

        mn = str(tmp_path / "m")
        ckpt.save_train_state(mn, {"w": jnp.full((4,), 1.0)})
        ckpt.save_train_state(mn, {"w": jnp.full((4,), 2.0)})
        r = ckpt.restore_train_state(mn, {"w": jnp.zeros((4,))})
        assert float(np.asarray(r["w"])[0]) == 2.0
        # no stray publish-window dirs after a clean save
        assert not os.path.isdir(ckpt.state_dir(mn) + ".new")
        assert not os.path.isdir(ckpt.state_dir(mn) + ".old")

    def test_crash_window_prefers_newer_complete_new(self, tmp_path):
        """With ``_state`` absent (crash between the two publish renames)
        ``.new`` is complete and one interval NEWER than the parked
        ``.old`` — restore must take it, and the next save must heal it
        into place instead of purging the store's only copies."""
        import jax.numpy as jnp

        mn = str(tmp_path / "m")
        path = ckpt.state_dir(mn)
        # fabricate the exact crash-window layout: v1 parked at .old,
        # v2 complete at .new, nothing published (saves heal the window,
        # so build the .old from a scratch model name)
        other = str(tmp_path / "other")
        ckpt.save_train_state(other, {"w": jnp.full((4,), 1.0)})
        os.rename(ckpt.state_dir(other), path + ".old")
        ckpt.save_train_state(mn, {"w": jnp.full((4,), 2.0)})
        os.rename(path, path + ".new")
        r = ckpt.restore_train_state(mn, {"w": jnp.zeros((4,))})
        assert float(np.asarray(r["w"])[0]) == 2.0  # the newer one
        # the next save heals rather than deletes: even a SIGKILL right
        # after its debris pass must leave a restorable state
        ckpt.save_train_state(mn, {"w": jnp.full((4,), 5.0)})
        r2 = ckpt.restore_train_state(mn, {"w": jnp.zeros((4,))})
        assert float(np.asarray(r2["w"])[0]) == 5.0

    def test_best_score_sidecar_roundtrip(self, tmp_path):
        mn = str(tmp_path / "m")
        assert ckpt.load_best_score(mn) == float("-inf")
        ckpt.save_best_score(mn, 17.5, step=123)
        assert ckpt.load_best_score(mn) == 17.5
        # unreadable sidecar degrades to -inf, never crashes a resume
        with open(ckpt.best_score_path(mn), "w") as f:
            f.write("{torn")
        assert ckpt.load_best_score(mn) == float("-inf")

    def test_restore_falls_back_across_crash_window(self, tmp_path):
        import jax.numpy as jnp

        mn = str(tmp_path / "m")
        ckpt.save_train_state(mn, {"w": jnp.full((4,), 3.0)})
        path = ckpt.state_dir(mn)
        # crash between the two publish renames: good state parked at .old
        os.rename(path, path + ".old")
        r = ckpt.restore_train_state(mn, {"w": jnp.zeros((4,))})
        assert float(np.asarray(r["w"])[0]) == 3.0
        # torn .new debris next to it must not poison the fallback
        os.makedirs(path + ".new")
        with open(os.path.join(path + ".new", "junk"), "w") as f:
            f.write("torn")
        r2 = ckpt.restore_train_state(mn, {"w": jnp.zeros((4,))})
        assert float(np.asarray(r2["w"])[0]) == 3.0
        # and the next save clears the debris and publishes cleanly
        ckpt.save_train_state(mn, {"w": jnp.full((4,), 4.0)})
        r3 = ckpt.restore_train_state(mn, {"w": jnp.zeros((4,))})
        assert float(np.asarray(r3["w"])[0]) == 4.0


# ---------------------------------------------------------------------------
# geometry validation (CheckpointMismatch)
# ---------------------------------------------------------------------------

class TestMismatch:
    def snap_of(self, **kw):
        mem = SharedReplay(**geom(16, **kw))
        fill(mem, 8)
        return mem.snapshot()

    def test_shape_change_fails_loudly(self):
        snap = self.snap_of()
        live = SharedReplay(**geom(16, shape=(5,)))
        with pytest.raises(ckpt.CheckpointMismatch, match="state rows"):
            ckpt.validate_snapshot(live, snap)

    def test_dtype_change_fails_loudly(self):
        snap = self.snap_of()
        live = SharedReplay(**geom(16, dtype=np.float32))
        with pytest.raises(ckpt.CheckpointMismatch, match="dtype"):
            ckpt.validate_snapshot(live, snap)

    def test_family_change_fails_loudly(self):
        snap = self.snap_of()
        live = SequenceReplay(capacity=8, seq_len=4, state_shape=(4,),
                              lstm_dim=3, state_dtype=np.float32)
        with pytest.raises(ckpt.CheckpointMismatch, match="segment"):
            ckpt.validate_snapshot(live, snap)

    def test_seq_len_change_fails_loudly(self):
        a = SequenceReplay(capacity=8, seq_len=4, state_shape=(4,),
                           lstm_dim=3, state_dtype=np.float32)
        a.feed(Segment(obs=np.zeros((5, 4), np.float32),
                       action=np.zeros(4, np.int32),
                       reward=np.zeros(4, np.float32),
                       terminal=np.zeros(4, np.float32),
                       mask=np.ones(4, np.float32),
                       c0=np.zeros(3, np.float32),
                       h0=np.zeros(3, np.float32)))
        live = SequenceReplay(capacity=8, seq_len=6, state_shape=(4,),
                              lstm_dim=3, state_dtype=np.float32)
        with pytest.raises(ckpt.CheckpointMismatch, match="obs rows"):
            ckpt.validate_snapshot(live, a.snapshot())

    def test_capacity_change_is_legal(self, tmp_path):
        mn = str(tmp_path / "m")
        mem = SharedReplay(**geom(32))
        fill(mem, 32)
        ckpt.save_epoch(mn, memory=mem, extras={"learner_step": 1})
        small = SharedReplay(**geom(8))
        # the reported count is what actually FIT, not the saved total
        assert ckpt.load_epoch_replay(ckpt.resolve_epoch(mn), small) == 8
        assert small.size == 8  # newest rows that fit

    def test_legacy_load_replay_validates_too(self, tmp_path):
        mn = str(tmp_path / "m")
        mem = SharedReplay(**geom(16))
        fill(mem, 8)
        ckpt.save_replay(mn, mem)
        live = SharedReplay(**geom(16, shape=(5,)))
        with pytest.raises(ckpt.CheckpointMismatch):
            ckpt.load_replay(mn, live)


# ---------------------------------------------------------------------------
# cross-family snapshot interchange (satellite: round-trips across
# memory families)
# ---------------------------------------------------------------------------

class TestCrossFamily:
    def test_host_per_to_device_per_leaf_agreement(self):
        import jax

        from pytorch_distributed_tpu.memory.device_per import DevicePerReplay

        host = PrioritizedReplay(**geom(64))
        fill(host, 30, priorities=True)
        host.update_priorities(np.arange(10),
                               np.linspace(0.2, 2.5, 10))
        snap = host.snapshot()
        dev = DevicePerReplay(**geom(64))
        dev.restore(snap)
        leaves_host = host.sum_tree.get(np.arange(host.size))
        leaves_dev = np.asarray(
            jax.device_get(dev.state.priority))[:host.size]
        np.testing.assert_allclose(leaves_dev, leaves_host, rtol=1e-5)
        # running max agrees in the shared base unit (device stores
        # p^alpha — memory/device_per.py snapshot/restore conversion)
        mx_dev = float(jax.device_get(dev.state.max_priority))
        np.testing.assert_allclose(mx_dev ** (1.0 / dev.alpha),
                                   host.max_priority, rtol=1e-5)

    def test_device_per_to_host_per_leaf_agreement(self):
        import jax

        from pytorch_distributed_tpu.memory.device_per import (
            DevicePerReplay, per_update_priorities,
        )

        dev = DevicePerReplay(**geom(64))
        rng = np.random.default_rng(0)
        n = 24
        dev.feed_chunk(Transition(
            state0=rng.integers(0, 255, (n, 4)).astype(np.uint8),
            action=np.zeros(n, np.int32),
            reward=np.arange(n, dtype=np.float32),
            gamma_n=np.full(n, 0.99, np.float32),
            state1=rng.integers(0, 255, (n, 4)).astype(np.uint8),
            terminal1=np.zeros(n, np.float32)))
        dev.state = per_update_priorities(
            dev.state, np.arange(n, dtype=np.int32),
            np.linspace(0.1, 3.0, n).astype(np.float32), alpha=dev.alpha)
        leaves_dev = np.asarray(jax.device_get(dev.state.priority))[:n]
        host = PrioritizedReplay(**geom(64))
        host.restore(dev.snapshot())
        assert host.size == n
        np.testing.assert_allclose(host.sum_tree.get(np.arange(n)),
                                   leaves_dev, rtol=1e-5)
        # both agree on what they'd sample
        batch = host.sample(8, np.random.default_rng(1))
        assert np.isfinite(batch.weight).all()

    def test_host_device_sequence_interchange(self):
        import jax

        from pytorch_distributed_tpu.memory.device_sequence import (
            DeviceSequenceReplay,
        )

        def seg(i):
            return Segment(
                obs=np.full((9, 4), float(i), np.float32),
                action=np.full(8, i, np.int32),
                reward=np.full(8, float(i), np.float32),
                terminal=np.zeros(8, np.float32),
                mask=np.ones(8, np.float32),
                c0=np.full(3, float(i), np.float32),
                h0=np.full(3, -float(i), np.float32))

        host = SequenceReplay(capacity=16, seq_len=8, state_shape=(4,),
                              lstm_dim=3, state_dtype=np.float32)
        for i in range(10):
            host.feed(seg(i))
        host.update_priorities(np.arange(10), np.linspace(0.1, 2.0, 10))
        dev = DeviceSequenceReplay(capacity=16, seq_len=8,
                                   state_shape=(4,), lstm_dim=3,
                                   state_dtype=np.float32)
        assert dev.restore(host.snapshot()) == 10
        st = jax.device_get(dev.state)
        np.testing.assert_allclose(np.asarray(st.reward)[:10, 0],
                                   np.arange(10, dtype=np.float32))
        np.testing.assert_allclose(np.asarray(st.priority)[:10],
                                   host.priority[:10], rtol=1e-5)
        # and back: the device snapshot refills a fresh host ring
        host2 = SequenceReplay(capacity=16, seq_len=8, state_shape=(4,),
                               lstm_dim=3, state_dtype=np.float32)
        assert host2.restore(dev.snapshot()) == 10
        np.testing.assert_allclose(host2.reward[:10, 0],
                                   np.arange(10, dtype=np.float32))
        np.testing.assert_allclose(host2.priority[:10], host.priority[:10],
                                   rtol=1e-5)

    def test_epoch_save_drains_queued_chunks(self, tmp_path):
        """Single-owner coordination: rows still sitting in the feeder
        queue at save time must land in the SAME epoch as the state."""
        mn = str(tmp_path / "m")
        owner = QueueOwner(SharedReplay(**geom(64)))
        feeder = owner.make_feeder(chunk=4)
        fill(feeder, 12)  # 3 flushed chunks, all still queued
        try:
            # mp.Queue delivers through a background feeder thread; wait
            # for the pipe (in the learner the drain cadence absorbs this)
            deadline = time.monotonic() + 10
            while owner.size < 12 and time.monotonic() < deadline:
                owner.drain()
                time.sleep(0.02)
            ckpt.save_epoch(mn, memory=owner, extras={"learner_step": 3})
            info = ckpt.resolve_epoch(mn)
            assert info.manifest["artifacts"]["replay.npz"]["rows"] == 12
            fresh = SharedReplay(**geom(64))
            assert ckpt.load_epoch_replay(info, fresh) == 12
        finally:
            owner.close()

    def test_field_check_contract(self):
        """The CI contract the tooling satellite adds — run it here so the
        fast tier catches a one-sided snapshot/restore surface at PR
        time, not at field time."""
        sys.path.insert(0, _REPO)
        from tools.field_check import check_snapshot_restore_contract

        out = check_snapshot_restore_contract()
        assert "SequenceReplay" in out["round_tripped"]
        assert out["scanned"] >= 8


# ---------------------------------------------------------------------------
# kill-resume drills (fast tier: checkpoint subsystem in a child process)
# ---------------------------------------------------------------------------

class TestKillDrill:
    # write points within the SECOND save (frames 6..11): the first epoch
    # is committed, then the process dies mid-Orbax-write (7), between
    # the state and replay writes (8), mid-replay-publish (9), with all
    # artifacts durable but uncommitted (10), and right after the
    # manifest commit (11)
    @pytest.mark.parametrize("frame", [7, 8, 9, 10, 11])
    @pytest.mark.timeout(240)
    def test_sigkill_mid_save_never_loses_the_store(self, tmp_path, frame):
        mn = str(tmp_path / "m")
        rc, out = run_child(KILL_CHILD, [mn, 4],
                            {"CKPT_FAULTS": f"kill@{frame}"})
        assert rc == -signal.SIGKILL, out
        committed = [int(line.split()[2]) for line in out.splitlines()
                     if line.startswith("COMMITTED")]
        assert committed, out  # the first save always survives
        # the surviving store: zero violations, a resolvable epoch whose
        # counters are one consistent triple
        rep = ckpt.fsck(ckpt.ckpt_root(mn))
        assert rep["violations"] == [], rep
        info = ckpt.resolve_epoch(mn)
        assert info is not None
        assert info.learner_step >= committed[-1]  # no regression
        assert info.extras["actor_step"] == info.learner_step * 3
        st = ckpt.load_epoch_state(info, tiny_state(0))
        assert int(st["step"]) == info.learner_step
        mem = SharedReplay(**geom(64))
        rows = ckpt.load_epoch_replay(info, mem)
        assert rows == mem.size > 0
        # a resumed writer clears the torn debris and continues numbering
        nxt = info.learner_step + 10
        ckpt.save_epoch(mn, state=tiny_state(nxt), memory=mem,
                        extras={"learner_step": nxt,
                                "actor_step": nxt * 3})
        assert ckpt.fsck(ckpt.ckpt_root(mn))["violations"] == []
        info2 = ckpt.resolve_epoch(mn)
        assert (info2.epoch, info2.learner_step) == (info.epoch + 1, nxt)


# ---------------------------------------------------------------------------
# full-topology drills (slow tier)
# ---------------------------------------------------------------------------

def _poll_epoch(model_name, timeout=300.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            info = ckpt.resolve_epoch(model_name)
        except Exception:  # noqa: BLE001 - GC race mid-poll
            info = None
        if info is not None:
            return info
        time.sleep(0.5)
    raise AssertionError(f"no complete epoch appeared under "
                         f"{ckpt.ckpt_root(model_name)}")


def _final_line(out):
    m = re.search(r"FINAL lstep=(\d+) actor=(\d+) preempted=(\d)", out)
    assert m, out
    return int(m.group(1)), int(m.group(2)), int(m.group(3))


class TestTopologyDrills:
    @pytest.mark.slow
    @pytest.mark.timeout(900)
    def test_sigkill_mid_save_then_resume_continues(self, tmp_path):
        """The acceptance drill: a real training run SIGKILLed between
        the state and replay writes of its second epoch save; the
        ``--resume`` run must find a complete digest-valid epoch and
        continue with learner step, replay size and clock counters
        mutually consistent."""
        mn = os.path.join(str(tmp_path), "models", "kr")
        # frame 8 = second save's after_state point (utils/checkpoint.py
        # _FRAME_POINTS): state durable, replay not yet written
        rc, out = run_child(TOPO_CHILD, [str(tmp_path), "kr", 60, "auto"],
                            {"CKPT_FAULTS": "kill@8"}, timeout=600)
        assert rc == -signal.SIGKILL, out
        rep = ckpt.fsck(ckpt.ckpt_root(mn))
        assert rep["violations"] == [], rep
        info = ckpt.resolve_epoch(mn)
        assert info is not None and info.learner_step > 0
        assert info.extras["replay_size"] > 0
        a1 = info.extras["actor_step"]

        rc2, out2 = run_child(TOPO_CHILD,
                              [str(tmp_path), "kr", 80, "must"],
                              timeout=600)
        assert rc2 == 0, out2
        assert "resumed epoch" in out2
        lstep, _actor, _pre = _final_line(out2)
        assert lstep >= 80
        final = ckpt.resolve_epoch(mn)
        assert final.learner_step >= 80 >= info.learner_step
        assert final.extras["actor_step"] >= a1  # counters never regress
        assert final.extras["replay_size"] > 0
        assert ckpt.fsck(ckpt.ckpt_root(mn))["violations"] == []

    @pytest.mark.slow
    @pytest.mark.timeout(900)
    def test_sigterm_preemption_writes_final_epoch_then_resumes(
            self, tmp_path):
        """SIGTERM = preemption notice (runtime.py): trip stop, drain,
        write a final epoch, exit 0 — and the next --resume run carries
        on from it."""
        mn = os.path.join(str(tmp_path), "models", "pt")
        proc = subprocess.Popen(
            [sys.executable, TOPO_CHILD, str(tmp_path), "pt", "1000000",
             "auto"],
            env=_child_env(), stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT)
        try:
            seen = _poll_epoch(mn, timeout=300.0)
            proc.send_signal(signal.SIGTERM)
            out = proc.communicate(timeout=300)[0].decode()
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.communicate(timeout=60)
        assert proc.returncode == 0, out
        assert "preemption notice" in out
        lstep, _actor, preempted = _final_line(out)
        assert preempted == 1
        final = ckpt.resolve_epoch(mn)
        # the final epoch is the preempted run's LAST state, not a stale
        # cadence save
        assert final.learner_step >= seen.learner_step
        assert final.learner_step >= lstep - 10  # within one cadence
        assert ckpt.fsck(ckpt.ckpt_root(mn))["violations"] == []

        rc2, out2 = run_child(
            TOPO_CHILD,
            [str(tmp_path), "pt", final.learner_step + 20, "must"],
            timeout=600)
        assert rc2 == 0, out2
        lstep2, _a2, _p2 = _final_line(out2)
        assert lstep2 >= final.learner_step + 20
        assert ckpt.resolve_epoch(mn).learner_step >= final.learner_step
