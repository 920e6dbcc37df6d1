"""Record ``scoped.xplane.pb``, the trace the tests of the scope reader
read: on one chip, run ``python3 bench/testdata/record_scoped.py <out>``.

A jitted program with two named scopes, ``head`` around a matmul and
``loop`` inside the body of a ``fori_loop`` of 8 steps, runs three times
inside a ``bench.window`` annotation, each run inside the program
recorder's ``engine.dispatch`` span and its result read to the host inside
``engine.wait``."""
import glob
import os
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "src"))
from repro.core import events  # noqa: E402


@jax.jit
def scoped(x):
    with jax.named_scope("head"):
        y = jnp.tanh(x @ x)

    def body(i, c):
        with jax.named_scope("loop"):
            return jnp.tanh(c @ x) + 1.0

    return lax.fori_loop(0, 8, body, y)


def main(out: str) -> None:
    x = jnp.full((512, 512), 1e-3, jnp.float32)
    float(scoped(x)[0, 0])                  # compile outside the trace
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"), events.recording():
        for _ in range(3):
            with events.span("engine.dispatch"):
                y = scoped(x)
            with events.span("engine.wait"):
                float(y[0, 0])
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(path, out)
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main(sys.argv[1])
