"""A scope's share of its roofline, in %: the least time the chip could
take for what the configuration's shapes ask of it (the larger of
operations over the bf16 peak and bytes over the HBM peak of
``benchmark/peaks.json``) over the device time measured under the scope
(``named_scopes.ms_per_step``).  The need is a function of
``benchmark/flops/`` named by the configuration's file::

    "<need>": {"function": "<flops file>", "kwargs": {...}}

and called as ``<flops file>.<need>_per_step(tokens=..., **kwargs)`` ->
``{"flops", "bytes"}``.  None without a trace, for a program without the
scope, or for a configuration that names no such need.
"""

from benchmark.lib import cells, device, named_scopes


def share(ctx: dict, scope: str, need: str):
    measured_ms = named_scopes.ms_per_step(ctx, scope)
    spec = (ctx.get("config") or {}).get(need)
    if not measured_ms or not spec:
        return None
    module = cells.load_module("flops", spec["function"])
    needed = getattr(module, f"{need}_per_step")(
        tokens=ctx["items_per_step"] // ctx["chips"], **spec["kwargs"]
    )
    peaks = device.load_peaks(ctx["device_kind"])
    least_s = max(
        needed["flops"] / peaks["bf16_flops_per_s"],
        needed["bytes"] / peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least_s / (1e-3 * measured_ms)
