"""Program spans on the profiler's timeline.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``; keyword arguments become the event's metadata, not part
of its name.  A span is recorded only while a profiler session runs
(``jax.profiler.start_trace``/``stop_trace``), on the same clock as the
device's operations; with none running it costs about a microsecond and
records nothing.  docs/serving.md lists the spans and what each bounds.
"""
from __future__ import annotations

import jax

PREFIX = "repro."


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
