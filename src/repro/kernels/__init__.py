"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel subpackage follows the mandated layout:

  kernels/<name>/kernel.py  — ``pl.pallas_call`` + explicit BlockSpec VMEM tiling
  kernels/<name>/ops.py     — jit'd public wrapper with TPU/CPU dispatch
  kernels/<name>/ref.py     — pure-jnp oracle

On a TPU the ops wrappers run the compiled Pallas kernels.  Elsewhere (the
CPU test host, the 512-device dry-run) they dispatch to the XLA reference
path; the Pallas bodies are validated in interpret mode by the test suite.
``REPRO_FORCE_PALLAS`` overrides the choice at trace time: ``interpret``
forces interpret-mode kernels everywhere (slow; tests only), ``never`` the
XLA reference even on a TPU (a reference leg; announced once on stderr).
``tests/test_tpu_compile.py`` compiles every kernel for a described v5e.
"""
import functools
import os
import sys

import jax


@functools.cache
def _announce_reference(backend: str) -> None:
    print(f"[kernels] REPRO_FORCE_PALLAS=never on {backend}: "
          "attention and scan ops run the XLA reference, not the Pallas "
          "kernels", file=sys.stderr, flush=True)


def use_pallas() -> bool:
    mode = os.environ.get("REPRO_FORCE_PALLAS", "auto")
    if mode in ("interpret", "always"):
        return True
    backend = jax.default_backend()
    if mode == "never":
        if backend == "tpu":
            _announce_reference(backend)
        return False
    return backend == "tpu"


def interpret_mode() -> bool:
    if os.environ.get("REPRO_FORCE_PALLAS") == "interpret":
        return True
    return jax.default_backend() != "tpu"
