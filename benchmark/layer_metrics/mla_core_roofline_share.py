"""Models and ops (``ops/attention.py``): the latent attention core's
share of its roofline, in %.

The least time the chip could take for the score and value products of
one step at 192 query/key and 128 value channels
(``benchmark/flops/kimi_linear.py::mla_core_per_step``: the causal half,
forward and backward, no recomputed scores and no padded channels; the
larger of operations over the bf16 peak and bytes over the HBM peak of
``benchmark/peaks.json``; the operations bind) over the device time
under the ``attention_core`` scope, which in this configuration only the
MLA layers run.  The measured time holds the recomputed forward pass and
the 256-deep products the kernels run for 192 channels; the need counts
neither, so the share cannot pass 100.
"""

from benchmark.lib import roofline


def read(ctx):
    return roofline.share(ctx, "attention_core", "mla_core")
