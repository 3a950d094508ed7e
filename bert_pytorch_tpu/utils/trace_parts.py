"""What a traced function's host time was spent on: the booking side.

``trace_s`` of a ``compile`` record (telemetry/compile_events.py) is host
Python: the model's own modules, the Pallas kernel bodies built at each
``pallas_call`` site, the optimizer over its leaves, and JAX's own passes.
The three hooks here stand where that work happens and book their intervals
into the monitored call on the thread's stack, if there is one:

* :func:`modules` round the model's ``apply`` (pretrain.py): ONE
  ``flax.linen.intercept_methods`` that books every module method's SELF time
  (its duration less what the intervals entered inside it cover) by class;
* :func:`kernel_build` round each ``pl.pallas_call(...)(...)`` (ops/pallas/)
  and megablox call (ops/moe.py), under the name the kernel already bears;
* :func:`optimizer` round the optimizer's update in the step.

All three run only while JAX TRACES the function they lie in, never on a
steady update. With no monitored call on the stack a hook is one read of a
thread-local and books nothing. The call is the dict
``telemetry.compile_events`` keeps in :data:`tls` while a function wrapped by
``CompileMonitor.instrument`` runs; the book is made at the call's first
hook. ``compile_events`` turns it into the record's ``trace_parts``; nothing
here imports ``telemetry/``, so ``ops/`` and ``pretrain.py`` need not either.
"""

from __future__ import annotations

import contextlib
import threading
import time

tls = threading.local()  # .call: the monitored call on this thread, if any

MODULE, KERNEL, OPTIMIZER = "module", "kernel", "optimizer"


class Book:
    """The intervals the hooks booked during one monitored call."""

    def __init__(self, clock=None):
        self.clock = clock or time.perf_counter
        # JAX stamps its trace spans with ``time.time``: what has to be
        # taken from one of them to land on this book's clock. An injected
        # clock is taken to be the spans' clock too.
        self.spans_ahead_s = (
            0.0 if clock not in (None, time.perf_counter)
            else time.time() - time.perf_counter())
        # entered and not left, outermost first: [start, its children's s]
        self._open = []
        self.booked = []     # (kind, name, self seconds), as they ended
        # each outermost interval: (start, end, len(booked) at its end)
        self.tops = []

    @contextlib.contextmanager
    def interval(self, kind: str, name: str):
        frame = [self.clock(), 0.0]
        self._open.append(frame)
        try:
            yield
        finally:
            end = self.clock()
            self._open.pop()
            whole = end - frame[0]
            self.booked.append((kind, name, whole - frame[1]))
            if self._open:
                self._open[-1][1] += whole
            else:
                self.tops.append((frame[0], end, len(self.booked)))

    def method(self, next_fun, args, kwargs, context):
        """The ``intercept_methods`` interceptor: one interval a method."""
        with self.interval(MODULE, type(context.module).__name__):
            return next_fun(*args, **kwargs)


def _book():
    call = getattr(tls, "call", None)
    if call is None:
        return None
    if call.get("book") is None:
        call["book"] = Book(call.get("clock"))
    return call["book"]


def modules():
    """Context manager round a model's ``apply`` (or ``init``)."""
    book = _book()
    if book is None:
        return contextlib.nullcontext()
    import flax.linen as nn

    return nn.intercept_methods(book.method)


def _interval(kind: str, name: str):
    book = _book()
    return (contextlib.nullcontext() if book is None
            else book.interval(kind, name))


def kernel_build(name: str):
    """Context manager round one ``pallas_call(...)(...)``: the kernel's body
    traced and its call bound, under the kernel's ``name``."""
    return _interval(KERNEL, name)


def optimizer():
    """Context manager round the optimizer's update in a train step."""
    return _interval(OPTIMIZER, "")
