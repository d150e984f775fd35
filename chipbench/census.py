"""What the benchmark counts and marks from outside the program.

* :class:`Compiles` counts JAX compilations (a persistent-cache load
  counts too) through ``jax.monitoring``, per program name; the harness
  reads it around the measured window, where it must stay at 0.
* :func:`spans` wraps callables of the program in
  ``jax.profiler.TraceAnnotation`` spans for a traced run, so the trace
  says what the host was doing while the device sat idle.  The wrappers
  only name the calls; they change no argument and no result.
"""
from __future__ import annotations

import contextlib
import functools

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Compiles:
    """Running count of compilations, by program name."""

    def __init__(self):
        import jax

        self.total = 0
        self.by_program: dict = {}
        self.live = True
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if not self.live or event != COMPILE_EVENT:
            return
        self.total += 1
        name = kw.get("fun_name", "?")
        n, s = self.by_program.get(name, (0, 0.0))
        self.by_program[name] = (n + 1, s + duration)

    def close(self):
        self.live = False


def _wrap(name, fn):
    import jax

    @functools.wraps(fn)
    def spanned(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)

    return spanned


@contextlib.contextmanager
def spans(targets):
    """Wrap ``(owner, attribute, span name)`` targets for the duration.

    A target the program no longer has is skipped: a span is a label in
    the breakdown, never a reason for a run to fail.
    """
    undo = []
    try:
        for owner, attr, name in targets:
            fn = getattr(owner, attr, None)
            if fn is None:
                continue
            undo.append((owner, attr, owner.__dict__.get(attr), fn))
            setattr(owner, attr, _wrap(name, fn))
        yield
    finally:
        for owner, attr, own, fn in reversed(undo):
            if own is None:
                delattr(owner, attr)
            else:
                setattr(owner, attr, own)
