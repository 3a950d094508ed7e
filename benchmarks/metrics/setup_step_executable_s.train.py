"""Seconds in JAX's compile-or-load call for ``train_step``
(``backend_compile_s`` of its ``compile`` and ``compile_cost`` records): the
cache entry's read on a warm start (``cache_load_s``), the XLA compile on a
cold one."""
from benchmarks.trace import startup


def read(ctx):
    return startup.value(ctx, "step_executable_s")
