"""Program spans on the profiler's clock.

``span(name, **ids)`` is ``jax.profiler.TraceAnnotation("repro." + name,
**ids)``.  A span is recorded exactly when a profiler session is open
(``jax.profiler.start_trace`` / ``trace``): it then lands in the same trace
as the device's operations, on the same clock (up to an offset of about a
millisecond on a v5e), and its ``ids`` arrive as the event's stats.  With
no session open a span costs about a microsecond and records nothing.
There is no other switch and no in-memory recorder.

Nesting on one thread makes the parent; spans on different threads are
linked by their ids.  ``docs/SERVING.md`` §"Spans" lists every span the
program records, and ``bench/spans.py`` reduces them.
"""
from __future__ import annotations

import jax

PREFIX = "repro."


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """A context manager recording ``repro.<name>`` with ``ids`` as stats.

    Ids known only at the end of the span are added inside it with
    ``set_metadata(**ids)`` on the object the ``with`` statement binds.
    """
    return jax.profiler.TraceAnnotation(PREFIX + name, **ids)
