"""Models and ops (``ops/linear_attention.py``): the chunk-wise gated
delta rule's share of its roofline, in %.

The least time the chip could take for the core of one step
(``benchmark/flops/olmo_hybrid.py::gdn_core_per_step`` from the
configuration's shapes and the step's tokens: the larger of operations
over the bf16 peak and bytes over the HBM peak of
``benchmark/peaks.json``; the bytes bind) over the device time under the
``gdn_core`` scope (``gdn_core_device_ms``).  That time holds the forward
pass twice where the blocks are recomputed, and the need counts it once:
the share is of what the model asks for, and cannot pass 100.  None for
a configuration that names no ``gdn_core`` need or a program without
the scope.
"""

from benchmark.lib import roofline


def read(ctx):
    return roofline.share(ctx, "gdn_core", "gdn_core")
