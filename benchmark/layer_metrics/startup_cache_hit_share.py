"""Start-up (``harness/startup.py``): warm or cold.  Of the compile
requests that went to the persistent cache between ``fit`` entry and the
end of the first chunk (``startup/compile_requests``), the share it
answered (``startup/cache_hits``), in %: 0 on a cold start; on a warm
one the share of programs the cache can hold at all (one that compiles
in under 0.5 s is never written).  None for a program that counts
neither, and at zero requests."""


def read(ctx):
    counters = ctx.get("counters", {})
    requests = counters.get("startup/compile_requests")
    hits = counters.get("startup/cache_hits")
    if not requests or hits is None:
        return None
    return 100.0 * hits / requests
