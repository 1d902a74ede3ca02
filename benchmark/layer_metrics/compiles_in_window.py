"""Compilations asked for between the window's two instants (jax
monitoring events: every compile request, whether the persistent cache
answers it or not; ``lib/compile_events.py``).  Expected 0 everywhere:
a compile inside the window is a fault of the warm-up."""


def read(ctx):
    value = ctx.get("compiles_in_window")
    return None if value is None else float(value)
