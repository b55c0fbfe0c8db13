"""Host spans written from the benchmark's own files into the profiler's
trace, around the calls into each layer of the program."""
from __future__ import annotations

PREFIX = "bench."


def span(name):
    import jax

    return jax.profiler.TraceAnnotation(PREFIX + name)
