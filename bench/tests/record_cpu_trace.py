#!/usr/bin/env python3
"""Records ``data/cpu_trace.xplane.pb``, the small CPU trace that
``test_trace.py`` reduces: three ``bench.step_once`` spans that each run a
program named ``sparse_fn`` and one named ``flash_like``, each followed by a
30 ms ``bench.wait`` span, all inside ``bench.window``.

    JAX_PLATFORMS=cpu python3 bench/tests/record_cpu_trace.py
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "data", "cpu_trace.xplane.pb")


@jax.jit
def sparse_fn(x):
    return jnp.tanh(x @ x.T).sum()


@jax.jit
def flash_like(x):
    return jax.nn.softmax(x @ x.T, axis=-1) @ x


def main():
    x = jnp.ones((256, 256))
    sparse_fn(x).block_until_ready()
    flash_like(x).block_until_ready()
    d = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(d, create_perfetto_link=False)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.step_once"):
                    sparse_fn(x).block_until_ready()
                    flash_like(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(0.03)
        jax.profiler.stop_trace()
        src = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                     "*.xplane.pb"))[0]
        os.makedirs(os.path.dirname(OUT), exist_ok=True)
        with open(src, "rb") as f:
            data = f.read()
        # the checkout's absolute path appears in source locations; put a
        # placeholder of the same length in its place, so that the
        # protobuf's length prefixes still hold
        root = os.path.dirname(os.path.dirname(HERE)).encode()
        with open(OUT, "wb") as f:
            f.write(data.replace(root, (b"<repo>" + b"_" * len(root))
                                 [:len(root)]))
    finally:
        shutil.rmtree(d, ignore_errors=True)
    print(OUT, os.path.getsize(OUT))


if __name__ == "__main__":
    sys.exit(main())
