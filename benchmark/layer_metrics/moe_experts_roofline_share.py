"""Models and ops (``parallel/moe.py``): the expert products' share of
their roofline, in %.

The least time the chip could take for the grouped products of one step
(``benchmark/flops/olmoe.py::expert_products_per_step`` from the
configuration's shapes and the step's tokens: the larger of operations
over the bf16 peak and bytes over the HBM peak of
``benchmark/peaks.json``) over the device time under the ``moe_experts``
scope (``moe_experts_device_ms``).  The scope also holds the SiLU gate
and the casts of the weight stacks, so the share is that of the kernels
together with what surrounds them; it cannot pass 100.
"""

from benchmark.lib import cells, device, named_scopes


def read(ctx):
    measured_ms = named_scopes.ms_per_step(ctx, "moe_experts")
    spec = (ctx.get("config") or {}).get("expert_products")
    if not measured_ms or not spec:
        return None
    module = cells.load_module("flops", spec["function"])
    need = module.expert_products_per_step(
        tokens=ctx["items_per_step"] // ctx["chips"], **spec["kwargs"]
    )
    peaks = device.load_peaks(ctx["device_kind"])
    least_s = max(
        need["flops"] / peaks["bf16_flops_per_s"],
        need["bytes"] / peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least_s / (1e-3 * measured_ms)
