"""Models and ops (``ops/ssm.py``): the chunk-wise state-space scan's
share of its roofline, in %.

The least time the chip could take for the scan of one step
(``benchmark/flops/granite_h.py::ssd_core_per_step`` from the
configuration's shapes and the step's tokens: the larger of operations
over the bf16 peak and bytes over the HBM peak of
``benchmark/peaks.json``) over the device time under the ``ssd_core``
scope (``ssd_core_device_ms``).  That time holds the forward pass twice
where the blocks are recomputed, and the need counts it once: the share
is of what the model asks for, and cannot pass 100.  None for a
configuration that names no ``ssd_core`` need or a program without the
scope.
"""

from benchmark.lib import roofline


def read(ctx):
    return roofline.share(ctx, "ssd_core", "ssd_core")
