#!/usr/bin/env python
"""Chip smoke: the quickest proof that the system still starts on the TPU.

Drives the flagship — config 12 (dqn / pong-sim / device-per / dqn-cnn) —
through the entry points a user calls, at full width: the Nature CNN in
bf16, batch 128, 84x84x4 uint8 frames, the default 50,000-row HBM
prioritized ring, the fused sample -> train -> priority-write-back
program at the learner's own ``steps_per_dispatch``, ``--backend
process`` (learner in the parent on the chip; logger, actor and
evaluator as spawned CPU children), the native C++ Pong stepper, async
parameter publication.  Only the step budget and ``learn_start`` are cut;
weights are random, made from the seed.

This process never imports JAX.  Every leg is a fresh OS process, run in
turn, so the chip has one owner at a time:

  kernels  tools/kernel_check.py — both Pallas kernels compiled for the
           TPU at production geometry, checked against their XLA
           references
  train    main.py --config 12 ... for STEPS learner steps, on every chip
           the machine shows (default ``--dp-size -1``)
  test     main.py --config 12 --mode 2 on the checkpoint ``train`` wrote

Each leg is judged from the program's own artifacts, not its exit code
alone: the learner's start-up record says ``platform=tpu`` (and which
sampler / mesh it selected for the device count it found) while every
child's says ``cpu``; ``scalars.jsonl`` shows frames collected and a
finite critic loss at the end of the step budget; the checkpoint epoch
is fsck-clean; mode 2 completes its episodes.  The first failing leg
ends the run with a non-zero exit code and no result line.

Legs run with ``JAX_PLATFORMS=tpu,cpu``: an explicit list fails loudly
where the default would carry on on the CPU, and the CPU backend stays
available to the host-pinned rollout paths.  An ambient
``JAX_PLATFORMS=cpu`` is not inherited.  The compile cache follows the
repo's one rule (``JAX_COMPILATION_CACHE_DIR`` if set, else
``<repo>/.jax_cache``), so a second run in the same place starts warm.
``native/build/`` and this script's previous ``logs/`` and ``models/``
are removed first: nothing the smoke reads predates it.

Last stdout line on success, with the device as JAX reported it:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
Per-leg wall times are printed above it (cold-compile time is set-up
time, not a rate; no rate is printed) and written, with the legs' logs,
under ``chiprun_out/chip_smoke/``.

Usage: python chip_smoke.py        (needs no arguments and no network)
"""

from __future__ import annotations

import ast
import glob
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
REFS = "chip_smoke"
STEPS = 320             # 10 dispatches at the TPU steps_per_dispatch of 32
TEST_EPISODES = 5
RING_BYTES = 2 * 50_000 * 4 * 84 * 84   # state0 + state1 rows, uint8
DEADLINE_S = 1100.0     # whole run, compile included (contract: 1200)
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

TRAIN_CMD = [
    "main.py", "--config", "12", "--num-actors", "1",
    "--num-envs-per-actor", "16", "--batch-size", "128",
    "--steps", str(STEPS), "--backend", "process", "--no-tensorboard",
    "--set", "learn_start=1000", "--set", f"refs={REFS}",
]
TEST_CMD = [
    "main.py", "--config", "12", "--mode", "2",
    "--model-file", os.path.join("models", REFS),
    "--set", f"tester_nepisodes={TEST_EPISODES}",
    "--set", f"refs={REFS}_test",
]


class LegFailed(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise LegFailed(msg)


def run(name: str, argv: list, t_end: float, platforms: str = "tpu,cpu"
        ) -> str:
    """One leg process, in its own process group so that every process it
    started is gone when this returns.  Returns its combined output."""
    env = dict(os.environ, JAX_PLATFORMS=platforms, PYTHONUNBUFFERED="1")
    log_path = os.path.join(OUT_DIR, f"{name}.log")
    budget = t_end - time.monotonic()
    check(budget > 5, f"no time left for {name} (deadline {DEADLINE_S}s)")
    with open(log_path, "w") as log:
        proc = subprocess.Popen([sys.executable] + argv, cwd=REPO, env=env,
                                stdout=log, stderr=subprocess.STDOUT,
                                start_new_session=True)
        try:
            rc = proc.wait(timeout=budget)
        except subprocess.TimeoutExpired:
            rc = None
        finally:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
    with open(log_path, errors="replace") as f:
        out = f.read()
    if rc != 0:
        sys.stderr.write("".join(out.splitlines(True)[-60:]))
        raise LegFailed(f"{name}: " + (f"exit code {rc}" if rc is not None
                                       else f"timed out after {budget:.0f}s"))
    return out


def last_json(out: str) -> dict:
    for line in reversed(out.splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise LegFailed("no JSON line in the leg's output")


def read_jsonl(path: str) -> list:
    check(os.path.exists(path), f"{path} was not written")
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def device_of(rec: dict) -> dict:
    return {"platform": rec["platform"], "kind": rec["device_kind"],
            "count": rec["device_count"]}


# -- legs --------------------------------------------------------------------

def leg_kernels(t_end: float) -> dict:
    rep = last_json(run("kernels", ["tools/kernel_check.py"], t_end))
    check(rep.get("ok") is True and rep["platform"] == "tpu",
          f"kernel_check reported {rep}")
    return rep


def leg_train(t_end: float) -> dict:
    run("train", TRAIN_CMD, t_end)
    log_dir = os.path.join(REPO, "logs", REFS)

    # who owned the chip: the learner, alone
    procs = {r["role"]: r for r in read_jsonl(
        os.path.join(log_dir, "startup.jsonl"))}
    for role in ("learner", "logger", "actor-0", "evaluator-0"):
        check(role in procs, f"no start-up record from {role}")
    learner = procs.pop("learner")
    check(learner["platform"] == "tpu",
          f"learner ran on {learner['platform']!r}, not the TPU")
    for role, r in procs.items():
        check(r["platform"] == "cpu",
              f"child {role} initialised the {r['platform']!r} backend")
    n = learner["device_count"]
    # what the learner selected for the device count it found: one chip
    # keeps the ring whole and samples with the Pallas kernel; more shard
    # ring rows over dp, which only the XLA sampler can address
    # and hands each chip its share of the batch (128 rows: CMD's)
    want = (dict(mesh=None, per_sampler="pallas", batch_rows="128x1")
            if n == 1 else
            dict(mesh={"dp": n}, per_sampler="xla",
                 batch_rows=f"{128 // n}x{n}dp"))
    want.update(steps_per_dispatch=32, torso="xla", publish="async")
    for k, v in want.items():
        check(learner[k] == v, f"learner start-up {k}={learner[k]!r}, "
                               f"expected {v!r} on {n} device(s)")
    hbm = learner["hbm_bytes_in_use"]
    check(len(hbm) == n and all(b and b >= 0.9 * RING_BYTES / n
                                for b in hbm),
          f"ring shards missing from a device: hbm_bytes_in_use={hbm}, "
          f"expected >= {RING_BYTES // n} on each of {n}")

    # what the run recorded about itself
    rows = read_jsonl(os.path.join(log_dir, "scalars.jsonl"))
    series = lambda tag: [r for r in rows if r.get("tag") == tag
                          and "value" in r]
    frames = series("actor/total_nframes")
    check(frames and sum(r["value"] for r in frames) > 0,
          "actor/total_nframes never rose above 0")
    loss = series("learner/critic_loss")
    check(loss and all(math.isfinite(r["value"]) for r in loss),
          f"learner/critic_loss missing or non-finite: "
          f"{[r['value'] for r in loss]}")
    check(loss[-1]["step"] >= STEPS,
          f"last critic_loss row is at step {loss[-1]['step']} < {STEPS}")

    # the checkpoint epoch it left (a host-side tool: keep it off the chip)
    fsck = json.loads(run(
        "fsck", ["tools/ckpt_fsck.py", "--require-complete",
                 os.path.join("models", REFS)],
        t_end, platforms="cpu").splitlines()[-1])[0]
    check(not fsck["violations"] and fsck["newest_complete"] is not None,
          f"checkpoint not fsck-clean: {fsck}")
    newest = [e for e in fsck["epochs"]
              if e["epoch"] == fsck["newest_complete"]][0]
    check(newest["learner_step"] >= STEPS,
          f"newest epoch is at learner_step {newest['learner_step']}")

    # utils/helpers.compile_cache_dir, restated: importing it imports JAX
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        REPO, ".jax_cache")
    check(os.path.isdir(cache) and os.listdir(cache),
          f"compile cache {cache} is empty after a TPU run")
    return {"learner": learner, "children": sorted(procs),
            "frames": sum(r["value"] for r in frames),
            "critic_loss": loss[-1]["value"],
            "epoch_step": newest["learner_step"], "cache_dir": cache,
            "cache_entries": len(os.listdir(cache))}


def leg_test(t_end: float) -> dict:
    out = run("test", TEST_CMD, t_end)
    line = [l for l in out.splitlines() if l.startswith("[tester] {")]
    check(bool(line), "mode 2 printed no [tester] summary")
    stats = ast.literal_eval(line[-1][len("[tester] "):])
    check(stats["nepisodes"] == TEST_EPISODES,
          f"mode 2 finished {stats['nepisodes']} of {TEST_EPISODES} episodes")
    check(-21.0 <= stats["avg_reward"] <= 21.0 and stats["avg_steps"] > 0,
          f"mode 2 stats out of range for Pong: {stats}")
    return stats


def main() -> int:
    t0 = time.monotonic()
    t_end = t0 + DEADLINE_S
    for stale in (glob.glob(os.path.join(REPO, "native", "build"))
                  + glob.glob(OUT_DIR)
                  + glob.glob(os.path.join(REPO, "logs", REFS + "*"))
                  + glob.glob(os.path.join(REPO, "models", REFS + "*"))):
        if os.path.isdir(stale):
            shutil.rmtree(stale)
        else:
            os.remove(stale)
    os.makedirs(OUT_DIR, exist_ok=True)

    report: dict = {"legs": {}}
    for name, leg in (("kernels", leg_kernels), ("train", leg_train),
                      ("test", leg_test)):
        t_leg = time.monotonic()
        try:
            result = leg(t_end)
        except LegFailed as e:
            print(f"[chip_smoke] leg {name} FAILED after "
                  f"{time.monotonic() - t_leg:.1f}s: {e}", file=sys.stderr)
            return 1
        wall = round(time.monotonic() - t_leg, 1)
        report["legs"][name] = dict(result, wall_s=wall)
        print(f"[chip_smoke] leg {name} ok in {wall}s", flush=True)

    kernels, train = report["legs"]["kernels"], report["legs"]["train"]
    device = device_of(train["learner"])
    if device != device_of(kernels):
        print(f"[chip_smoke] legs disagree on the device: {device} vs "
              f"{device_of(kernels)}", file=sys.stderr)
        return 1
    report.update(device=device, wall_s=round(time.monotonic() - t0, 1),
                  claim=None)
    with open(os.path.join(OUT_DIR, "report.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(f"[chip_smoke] platform={device['platform']} "
          f"device_kind={device['kind']!r} devices={device['count']} "
          f"total {report['wall_s']}s (compile included)")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
