#!/usr/bin/env python3
"""Re-record ``tiny_tpu_scoped.xplane.pb``: a few calls of a small jitted
program on one chip that enters two of the program's scopes
(``train.online`` around a differentiated ``while``, so a
``transpose(jvp(...))`` path exists, and ``train.optimizer`` after it) and
leaves a transpose outside any scope.  Run on the chip:

    python3 benchmark/testdata/record_scoped.py [out_dir]

benchmark/tests/test_phases.py checks harness/phases.py against sums worked
out by hand from this file's events; the listing this prints (every device
op with its ``tf_op`` path, category and times) is what they were worked
out from.  ``record.py`` and its trace stay as they are: a trace WITHOUT
scopes is the other half of the test.
"""

import glob
import os
import shutil
import sys
import time


def main() -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform == "cpu":
        sys.exit("record_scoped.py needs the chip: a CPU trace has no "
                 "device plane")
    out = sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/testdata"
    os.makedirs(out, exist_ok=True)

    @jax.jit
    def tiny_scoped_step(w, x):
        def loss_fn(w):
            with jax.named_scope("train.online"):
                def body(c, _):
                    return jnp.tanh(c @ w) * 0.5, None
                y, _ = jax.lax.scan(body, x, None, length=3)
                return jnp.mean(jnp.square(y.astype(jnp.float32)))

        loss, grad = jax.value_and_grad(loss_fn)(w)
        with jax.named_scope("train.optimizer"):
            w = w - 0.01 * grad
        # under no scope: a transpose the compiler files under "data
        # formatting" (the sqrt it fuses into an op of the scope above)
        return w, jnp.sqrt(loss), jnp.transpose(x)

    w = jnp.eye(256, dtype=jnp.bfloat16)
    x = jnp.ones((256, 256), jnp.bfloat16)
    jax.block_until_ready(tiny_scoped_step(w, x))
    tmp = os.path.join(out, "_trace")
    shutil.rmtree(tmp, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench/window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench/dispatch"):
                w, loss, _xt = tiny_scoped_step(w, x)
            loss.block_until_ready()
            with jax.profiler.TraceAnnotation("bench/pause"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    src = sorted(glob.glob(os.path.join(
        tmp, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    dst = os.path.join(out, "tiny_tpu_scoped.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    print(dst, os.path.getsize(dst), "bytes")
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.harness import phases, trace

    print(trace.describe(dst))
    devices, window = phases.load(dst)
    print("window", window)
    for d in devices:
        for line, events in (("module", d.modules), ("op", d.ops)):
            for meta_id, start, end in events:
                m = d.meta[meta_id]
                print(line, trace.op_name(m.name)[:48], round(start, 3),
                      round(end, 3), repr(m.tf_op), repr(m.category))
    print(phases.per_update_ms(devices, window, ["jit_tiny_scoped_step"], 1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
