"""How ``recorded_v5e.xplane.pb`` was made (on the chip, PR 23): a few
steps of a small jitted program under the harness's span names, so the
trace reduction has a real device trace to be checked against.

    chiprun -- python3 perfbench/tests/record_trace.py chiprun_out/recorded_v5e.xplane.pb
"""

import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main(out_path: str) -> None:
    import jax
    import jax.numpy as jnp

    from perfbench import trace

    def pb_train_step(x):
        return jnp.tanh(x @ x) * 0.5

    def pb_fork(xs):
        return [jnp.copy(x) for x in xs]

    step, fork = jax.jit(pb_train_step), jax.jit(pb_fork)
    x = jax.block_until_ready(step(jnp.ones((1024, 1024), jnp.bfloat16)))
    jax.block_until_ready(fork([x, x]))
    trace_dir = tempfile.mkdtemp(prefix="pb-recorded-")
    jax.profiler.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation("pb.traced"):
        with jax.profiler.TraceAnnotation("pb.async_take"):
            forked = fork([x, x])
        for _ in range(3):
            with jax.profiler.TraceAnnotation("pb.step"):
                x = step(x)
                with jax.profiler.TraceAnnotation("pb.step.block"):
                    jax.block_until_ready(x)
        jax.block_until_ready(forked)
    jax.profiler.stop_trace()
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    shutil.copy(trace.find_xplane(trace_dir), out_path)
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(os.path.getsize(out_path), "bytes ->", out_path)


if __name__ == "__main__":
    main(sys.argv[1])
