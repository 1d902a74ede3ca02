"""Models / ops: model FLOP/s utilization over the window.

The configuration file's model FLOPs per item (an analytic count kept
with the benchmark, ``benchmark/flops/``) x measured items per second
over chips x the bf16 peak of ``benchmark/peaks.json``.  It is
the cell's items per second times a constant, which is why it is a per-layer
metric and not an end-to-end one.
"""

from benchmark.lib import cells, device


def read(ctx):
    if "items_per_s" not in ctx:
        return None
    flops = cells.flops_per_item(ctx["config"])
    if flops is None:
        return None
    peak = device.load_peaks(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops * ctx["items_per_s"] / (ctx["chips"] * peak)
