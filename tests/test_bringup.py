"""Bring-up policy (ISSUE 21): where the compile cache lives, what
environment a CPU worker is exec'd with, and that a run the monitor
stopped is reported as failed.  Pure Python — nothing here compiles."""

import os
import tempfile
import threading
import types

import pytest

from pytorch_distributed_tpu import runtime
from pytorch_distributed_tpu.utils import flight_recorder, helpers

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestCompileCacheDir:
    def test_env_set_is_used_verbatim(self):
        assert helpers.compile_cache_dir(
            {"JAX_COMPILATION_CACHE_DIR": "/some/dir"}) == "/some/dir"

    def test_unset_is_a_fixed_directory_in_the_checkout(self, monkeypatch):
        want = os.path.join(REPO, ".jax_cache")
        assert helpers.compile_cache_dir({}) == want
        # independent of pid and time, never under the temp dir
        monkeypatch.setattr(os, "getpid", lambda: 424242)
        monkeypatch.setattr("time.time", lambda: 1e9)
        assert helpers.compile_cache_dir({}) == want
        assert not want.startswith(tempfile.gettempdir() + os.sep)
        # an empty value is "unset", not the current directory
        assert helpers.compile_cache_dir(
            {"JAX_COMPILATION_CACHE_DIR": ""}) == want

    def test_reads_the_process_environment_by_default(self, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert helpers.compile_cache_dir() == "/elsewhere"

    def test_cache_directory_is_git_ignored(self):
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert "/.jax_cache/" in f.read().split()


class TestCpuChildEnv:
    def test_children_exec_as_cpu_without_the_cache(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        with runtime.cpu_child_env():
            assert os.environ["JAX_PLATFORMS"] == "cpu"
            assert "JAX_COMPILATION_CACHE_DIR" not in os.environ
        assert os.environ["JAX_PLATFORMS"] == "tpu,cpu"
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/some/dir"

    def test_restores_an_unset_parent_environment(self, monkeypatch):
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        with pytest.raises(RuntimeError):
            with runtime.cpu_child_env():
                raise RuntimeError("spawn failed")
        assert "JAX_PLATFORMS" not in os.environ
        assert "JAX_COMPILATION_CACHE_DIR" not in os.environ


def _monitored_topology(proc_meta, inference_server=None):
    """A Topology with exactly the state ``_monitor`` reads — no env
    probe, no model init, no spawn."""
    topo = runtime.Topology.__new__(runtime.Topology)
    topo.clock = types.SimpleNamespace(stop=threading.Event())
    topo.inference_server = inference_server
    topo.health = types.SimpleNamespace(hang_deadline=0, hang_grace=0)
    topo.stop_reason = None
    topo._restart_budget = None
    topo._workers = [m[0] for m in proc_meta]
    topo._proc_meta = list(proc_meta)
    return topo


class TestStopReason:
    @pytest.fixture(autouse=True)
    def _no_blackbox(self):
        flight_recorder.reset()
        yield
        flight_recorder.reset()

    def test_dead_logger_is_worker_fatal(self):
        dead = types.SimpleNamespace(exitcode=1, name="logger-0")
        live = types.SimpleNamespace(exitcode=None, name="actor-0")
        topo = _monitored_topology([(live, "actor", 0, ()),
                                    (dead, "logger", 0, ())])
        topo._monitor(poll=0.01)
        assert topo.clock.stop.is_set()
        assert topo.stop_reason == "worker-fatal"
        assert topo.stop_reason in runtime.FATAL_STOP_REASONS

    def test_dead_inference_server_is_fatal(self):
        srv = types.SimpleNamespace(healthy=lambda: False)
        topo = _monitored_topology([], inference_server=srv)
        topo._monitor(poll=0.01)
        assert topo.clock.stop.is_set()
        assert topo.stop_reason == "inference-server-dead"
        assert topo.stop_reason in runtime.FATAL_STOP_REASONS

    def test_a_finished_run_has_no_stop_reason(self):
        done = types.SimpleNamespace(exitcode=0, name="logger-0")
        topo = _monitored_topology([(done, "logger", 0, ())])
        t = threading.Thread(target=topo._monitor, args=(0.01,))
        t.start()
        topo.clock.stop.set()  # the learner reaching its own end
        t.join(5.0)
        assert not t.is_alive()
        assert topo.stop_reason is None

    def test_main_exits_nonzero_on_a_fatal_stop(self, monkeypatch):
        import main

        monkeypatch.setattr(helpers, "enable_compile_cache", lambda: None)
        monkeypatch.setattr(
            runtime, "train", lambda opt, backend: types.SimpleNamespace(
                stop_reason="worker-fatal"))
        with pytest.raises(SystemExit) as e:
            main.main(["--config", "1"])
        assert e.value.code not in (0, None)
        monkeypatch.setattr(
            runtime, "train", lambda opt, backend: types.SimpleNamespace(
                stop_reason=None))
        main.main(["--config", "1"])  # a finished run returns normally
