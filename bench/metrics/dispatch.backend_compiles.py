"""Executables built per query of the window: the program's
``dispatch_backend_compiles`` counter (each an XLA compile or a load
from the persistent compilation cache, as JAX's backend-compile event
reports them), from the window's ``query`` spans. A kernel shape first
dispatched to a device it never ran on builds one. The warm-up aims to
leave none."""
from bench.program_counters import recorded


def read(run):
    """Executables built per query."""
    return recorded(run, "dispatch_backend_compiles")
