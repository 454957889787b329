"""Host spans of the served path, on the profiler's clock.

``span(name, **stats)`` is a ``jax.profiler.TraceAnnotation``: while a
profiler trace runs, the span lands on the host plane beside the device's
events, with ``stats`` (ints or short strings) as its event stats, and its
``set_metadata(**stats)`` adds stats known only at the span's end.  With
no trace running a span costs about a microsecond.  Spans go in host code
only, never inside a jitted function, and compute nothing that waits for
the device.  Names start with ``repro.<layer>.``.

JAX is imported at the first span, not with this module: the cluster
layer, which the simulator shares with the live executor, imports no JAX.
"""
import functools


@functools.cache
def _annotation():
    from jax.profiler import TraceAnnotation
    return TraceAnnotation


def span(name: str, **stats):
    return _annotation()(name, **stats)
