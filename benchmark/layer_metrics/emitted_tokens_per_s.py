"""Serve scheduler: tokens the scheduler emitted between the window's
two instants over the window, from the program's ``serve/tokens``
counter (one increment per token of every request, finished or not).
The end-to-end ``serve_tokens_per_s`` is the benchmark's own count of
the tokens of requests completed in the window; in a closed loop the
two differ by the requests in flight at either end."""

from benchmark.lib import stats


def read(ctx):
    a, b = ctx["snap1"], ctx["snap0"]
    if "serve/tokens" not in a:
        return None
    return stats.delta(a, b, "serve/tokens") / ctx["window_s"]
