"""Host spans the harness puts around its calls into the program.

Each span is timed on the host clock and, while a trace is being taken,
also written into the trace as a ``TraceAnnotation`` of the same name, so
the trace reduction can say what the host was doing in each device gap."""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List


class Spans:
    def __init__(self, traced: bool):
        self.traced = traced
        self.seconds: Dict[str, List[float]] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        if self.traced:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t = time.perf_counter()
        try:
            yield
        finally:
            self.seconds.setdefault(name, []).append(time.perf_counter() - t)
            if self.traced:
                ann.__exit__(None, None, None)

    def total(self, name: str) -> float:
        return sum(self.seconds.get(name, ()))
