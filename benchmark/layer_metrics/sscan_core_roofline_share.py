"""Models and ops (``ops/selective_scan.py``): the selective scan's share
of its roofline, in %.

The least time the chip could take for the scan of one step
(``benchmark/flops/phi4_flash.py::sscan_core_per_step`` from the
configuration's shapes and the step's tokens: the larger of operations
over the bf16 peak and bytes over the HBM peak of
``benchmark/peaks.json``; here the bytes) over the device time under the
``sscan_core`` scope (``sscan_core_device_ms``).  The scan is element-wise
work on the vector unit, which neither peak describes, so the share reads
low by design.  That time holds the forward pass twice where the blocks
are recomputed, and the need counts it once: the share is of what the
model asks for, and cannot pass 100.  None for a configuration that names
no ``sscan_core`` need or a program without the scope.
"""

from benchmark.lib import roofline


def read(ctx):
    return roofline.share(ctx, "sscan_core", "sscan_core")
