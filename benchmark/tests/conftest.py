"""CPU rehearsals of the benchmark.  Run with

    python -m pytest benchmark/tests -q

Nothing here is a measurement: a rehearsal proves paths, arguments, control
flow and the shape of the result line at a tiny size, and its line carries
no metric value (harness/cell.py).  The real entry, benchmark/run.py, is
never given a way to run without a chip; the rehearsals go through
``rehearse()`` below, which exists only here.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY_CONFIGS = {
    "tiny_apex": {
        "row": 12, "family": "dqn", "fill_chunk": 128,
        "overrides": {"memory_size": 512, "batch_size": 8},
        "shapes": {"batch_size": 8, "double": False},
        "reference": "dqn", "reference_hyper": {"double": False},
    },
    "tiny_r2d2": {
        "row": 14, "family": "r2d2", "fill_chunk": 16,
        "overrides": {"memory_size": 256, "batch_size": 4, "seq_len": 8,
                      "seq_overlap": 4, "burn_in": 2, "nstep": 2},
        "shapes": {"batch_size": 4, "seq_len": 8, "burn_in": 2,
                   "lstm_dim": 512},
        "reference": "r2d2",
        "reference_hyper": {"burn_in": 2, "nstep": 2, "gamma": 0.99,
                            "eta": 0.9, "double": True,
                            "value_rescale": True, "pack_frames": 4},
    },
}
# float32 on the CPU (bf16 compute stays the model's): far tighter would
# pass; these only have to catch a wrong term
TINY_TOLERANCE = {"loss_rel": 0.1, "td_p90_over_mean": 0.5, "grad_cosine": 0.95,
                  "unchanged_rows": 2, "why": "CPU rehearsal"}
TINY_TRAFFIC = {
    "tiny_learner_only": {
        "runner": "learner_only", "warm_dispatches": 2, "max_in_flight": 2,
        "trace_seconds": 0.2, "trace_min_dispatches": 2,
        "trace_max_seconds": 2.0, "step_modules": ["jit_one", "jit_multi"],
    },
}
TINY_CELLS = [  # name, config, traffic, chips, the real cell it stands for
    ("tiny_apex.tiny_learner_only", "tiny_apex", "tiny_learner_only", 1,
     "apex_pong.learner_only"),
    ("tiny_r2d2.tiny_learner_only", "tiny_r2d2", "tiny_learner_only", 1,
     "r2d2_pong.learner_only"),
    ("tiny_apex_dp4.tiny_learner_only", "tiny_apex_dp4",
     "tiny_learner_only", 4, "apex_pong_dp4.learner_only"),
]


def add_cell(root: str, name: str, config: str, traffic: str, chips: int,
             like: str, config_body: dict = None,
             traffic_body: dict = None) -> None:
    """Add a cell to the copy of the benchmark under ``root`` the way a
    later PR would: new files and new entries, no edit to a file under
    benchmark/ that is there.  A metric that lists its cells and lists
    ``like`` lists the new cell too."""
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    if config_body is not None:
        rel = f"benchmark/configs/{config}.json"
        with open(os.path.join(root, rel), "x") as f:
            json.dump(config_body, f)
        manifest["configs"].append({
            "name": config, "source": "test", "file": rel, "reduced": [],
            "why": "test"})
    if traffic_body is not None:
        with open(os.path.join(root, "benchmark", "traffic",
                               f"{traffic}.json"), "x") as f:
            json.dump(traffic_body, f)
    manifest["workloads"].append({"name": name, "config": config,
                                  "traffic": traffic, "chips": chips,
                                  "why": "test"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(name)
    with open(path, "w") as f:
        json.dump(manifest, f, indent=1)


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> str:
    """A copy of BENCHMARK.json and benchmark/ with the tiny cells added."""
    root = str(tmp_path_factory.mktemp("bench"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    written = set()
    for name, config, traffic, chips, like in TINY_CELLS:
        base = config.replace("_dp4", "")
        body = dict(TINY_CONFIGS[base], tolerance=TINY_TOLERANCE)
        add_cell(root, name, config, traffic, chips, like,
                 config_body=None if config in written else body,
                 traffic_body=None if traffic in written
                 else TINY_TRAFFIC[traffic])
        written |= {config, traffic}
    return root


_ENTRY = """
import sys, time
t = time.perf_counter()
from benchmark.harness import cell
sys.exit(cell.main(sys.argv[1:], t, require_accelerator=False))
"""


def rehearse(root: str, workload: str, chips: int = 1, trace: int = 0,
             seconds: float = 1.0, seed: int = 1, timeout: float = 600.0):
    """Run one cell of the benchmark under ``root`` in a fresh process on
    the CPU backend (``chips`` virtual devices).  Returns the finished
    process; its last stdout line is the result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}",
               PYTHONPATH=os.pathsep.join([root, REPO]))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, "-c", _ENTRY, "--workload", workload, "--seed",
         str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout)
