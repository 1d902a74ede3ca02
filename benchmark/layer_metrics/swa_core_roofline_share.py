"""Models and ops (``ops/attention.py``): the window layers' attention
core's share of its roofline, in %.

The least time the chip could take for the window layers' cores of one
step (``benchmark/flops/phi4_flash.py::swa_core_per_step`` from the
configuration's shapes and the step's tokens: the larger of operations
over the bf16 peak and bytes over the HBM peak of
``benchmark/peaks.json``; here the operations, of the keys a query sees
and no more) over the device time under the ``swa_core`` scope
(``swa_core_device_ms``).  That time holds the forward pass twice where
the blocks are recomputed, the masked halves of the tiles that straddle
the window and the padded query/key channels, and the need counts none of
them: the share is of what the model asks for, and cannot pass 100.  None
for a configuration that names no ``swa_core`` need or a program without
the scope.
"""

from benchmark.lib import roofline


def read(ctx):
    return roofline.share(ctx, "swa_core", "swa_core")
