#!/usr/bin/env python3
"""Re-record ``tiny_tpu.xplane.pb``: a few calls of a small jitted program on
one chip, with a ``while`` (an op that encloses others), gaps between the
calls and the benchmark's own ``bench/`` host spans.  Run on the chip:

    python3 benchmark/testdata/record.py [out_dir]

Small on purpose: benchmark/tests/test_trace.py checks the reduction against
numbers worked out by hand from this file's events.
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
        sys.exit("record.py needs the chip: a CPU trace has no device plane")
    out = sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/testdata"
    os.makedirs(out, exist_ok=True)

    @jax.jit
    def tiny_step(x):
        def body(c, _):
            return jnp.tanh(c @ c) * 0.5, None
        y, _ = jax.lax.scan(body, x, None, length=3)
        return y.sum()

    x = jnp.ones((256, 256), jnp.bfloat16)
    tiny_step(x).block_until_ready()
    tmp = os.path.join(out, "_trace")
    shutil.rmtree(tmp, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench/window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench/dispatch"):
                y = tiny_step(x)
            y.block_until_ready()
            with jax.profiler.TraceAnnotation("bench/pause"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    src = sorted(glob.glob(os.path.join(
        tmp, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    dst = os.path.join(out, "tiny_tpu.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    print(dst, os.path.getsize(dst), "bytes")
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.harness import trace

    print(trace.describe(dst))
    return 0


if __name__ == "__main__":
    sys.exit(main())
