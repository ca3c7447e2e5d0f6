"""Program spans on the profiler's clock.

`span()` opens a `jax.profiler.TraceAnnotation` while the process-wide
`TRACER` is enabled: the span lands on the profiler's host plane, one
line per thread, with its keyword arguments as event stats and on the
same timebase as the device lanes.  Disabled (the default), a span
costs one attribute read and returns a shared no-op context manager;
no annotation is opened, since one costs time even with no profiler
session running.

Spans nest per thread: a served round shows ``frontend.round`` over
``service.get`` over its ``service.prepare`` / ``service.dispatch`` /
``service.readback`` / ``service.refine`` steps, with the device
program's ``dispatch.*`` entry inside the dispatch step.

Typical use, with the device lanes beside the spans::

    import jax
    from repro.obs import trace
    trace.TRACER.enable()
    with jax.profiler.trace("trace_dir", create_perfetto_trace=True):
        ... run workload ...
    trace.TRACER.disable()
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class Tracer:
    """The gate of the program's spans: off by default."""

    def __init__(self):
        self._enabled = False

    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self) -> None:
        self._enabled = True

    def disable(self) -> None:
        self._enabled = False

    def span(self, name: str, cat: str = "", **args):
        """Context manager annotating its body on the profiler's host
        plane (``cat``, when given, travels as a stat).  The shared null
        object while the tracer is disabled."""
        if not self._enabled:
            return _NULL_SPAN
        if cat:
            args["cat"] = cat
        return TraceAnnotation(name, **args)

    def instant(self, name: str, cat: str = "", **args) -> None:
        """A span that closes as it opens: a point event on the calling
        thread."""
        if self._enabled:
            with self.span(name, cat, **args):
                pass


# the process-wide tracer every instrumented layer records into
TRACER = Tracer()


def span(name: str, cat: str = "", **args):
    if not TRACER._enabled:  # the disabled path: no second call
        return _NULL_SPAN
    return TRACER.span(name, cat, **args)


def instant(name: str, cat: str = "", **args) -> None:
    if TRACER._enabled:
        TRACER.instant(name, cat, **args)
