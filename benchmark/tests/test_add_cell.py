"""A later PR adds a model family, a configuration, a traffic mix, per-layer
metrics and a cell as NEW files plus entries of BENCHMARK.json, and edits no
file that is there.  Shown on a copy: the files under benchmark/ are hashed
before and after."""

import hashlib
import json
import os

from conftest import TINY_CONFIGS, TINY_TOLERANCE, TINY_TRAFFIC, add_cell, \
    rehearse
from test_cells import detail_of, last_line

NEW_METRIC = '''"""Dispatches completed inside the window (a count)."""

METRIC = {"layer": "dispatch_loop", "unit": "dispatches", "better": "higher",
          "source": "program_counter", "moves": "updates_per_s"}


def read(ctx):
    return ctx.result.notes.get("dispatches_in_window")
'''


# what a model_config PR brings for a model the benchmark has not seen: its
# own FLOPs count and its own seeded rows; the ring code it shares with an
# existing family it imports from that family's file
NEW_FAMILY = '''"""Family ``new_family``."""

import sys

from . import dqn
from .dqn import agrees, build_step, update_priorities  # noqa: F401


def update_flops(shapes, state_shape, num_actions):
    print("[new_family] update_flops", shapes["width"], file=sys.stderr)
    return 1000 * shapes["width"] * shapes["batch_size"]


def seed_chunk(key, n, lrn):
    print("[new_family] seed_chunk", file=sys.stderr)
    chunk = dqn.seed_chunk(key, n, lrn)
    return chunk._replace(reward=0.5 * chunk.reward)
'''

FLOPS_METRIC = '''"""GFLOP one update needs, by the family's count."""

METRIC = {"layer": "fused_step", "unit": "GFLOP", "better": "lower",
          "source": "program_counter", "moves": "updates_per_s"}


def read(ctx):
    return ctx.flops_per_update() / 1e9
'''


def digests(root: str) -> dict:
    out = {}
    for d, _dirs, files in os.walk(os.path.join(root, "benchmark")):
        if "__pycache__" in d:
            continue
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[path] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_add_a_cell_as_files_only(tiny_root):
    before = digests(tiny_root)
    config = dict(TINY_CONFIGS["tiny_apex"], tolerance=TINY_TOLERANCE,
                  family="new_family", fill_chunk=64,
                  overrides={"memory_size": 256, "batch_size": 4,
                             "enable_double": True},
                  shapes={"batch_size": 4, "width": 3},
                  reference_hyper={"double": True})
    traffic = dict(TINY_TRAFFIC["tiny_learner_only"], warm_dispatches=3)
    new_files = {"families/new_family.py": NEW_FAMILY,
                 "layer_metrics/dispatches_done.py": NEW_METRIC,
                 "layer_metrics/update_gflop.py": FLOPS_METRIC}
    for rel, body in new_files.items():
        with open(os.path.join(tiny_root, "benchmark", rel), "x") as f:
            f.write(body)
    add_cell(tiny_root, "new_model.new_mix", "new_model", "new_mix", 1,
             like="apex_pong.learner_only", config_body=config,
             traffic_body=traffic)
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    for name, unit in (("dispatches_done", "dispatches"),
                       ("update_gflop", "GFLOP")):
        manifest["per_layer"].append({
            "name": name, "unit": unit, "better": "higher",
            "source": "program_counter", "layer": "fused_step",
            "moves": "updates_per_s", "workloads": ["new_model.new_mix"]})
    with open(path, "w") as f:
        json.dump(manifest, f)

    proc = rehearse(tiny_root, "new_model.new_mix", trace=1, seconds=1.5)
    line = last_line(proc)
    assert line["correct"] is True
    # the new family's own rows filled the ring, and its own count was read
    assert "[new_family] seed_chunk" in proc.stderr
    assert "[new_family] update_flops 3" in proc.stderr
    assert {"dispatches_done", "update_gflop"} <= set(
        detail_of(proc)["rehearsal_metric_names"])
    after = digests(tiny_root)
    assert {p: h for p, h in after.items() if p in before} == before
    # config, traffic, family, two metrics
    assert len(after) == len(before) + 5
