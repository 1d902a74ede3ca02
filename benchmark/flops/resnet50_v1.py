"""Model FLOPs of one ResNet-50 v1 training image, counted from shapes.

He et al. 2015 (arXiv:1512.03385), Table 1, 50-layer column: a 7x7/2
stem of 64 channels, a 3x3/2 max pool, four stages of 3, 4, 6 and 3
bottleneck blocks (1x1 -> 3x3 -> 1x1 with widths w, w, 4w for w = 64,
128, 256, 512), global average pooling and a 1000-way classifier.  The
first block of stages 2-4 halves the resolution and its shortcut is a
strided 1x1 projection.  ``stride_on`` says which convolution of that
block carries the stride: ``"conv1"`` is the paper's placement (first
1x1; 3.86 GMAC forward at 224x224), ``"conv2"`` the torchvision/Keras
placement on the 3x3 that ``models/resnet.py`` uses (4.09 GMAC).

Only multiply-accumulates of convolutions and the classifier count (a
MAC is 2 FLOPs); batch norm, ReLU and pooling are left out, as is
usual.  Forward + backward is taken as 3x forward (the backward pass
computes a gradient for the input and one for the weights).
"""

from __future__ import annotations


def forward_macs(image_size: int = 224, num_classes: int = 1000,
                 stride_on: str = "conv2") -> int:
    macs = 0
    size = image_size // 2  # 7x7 stride 2, SAME
    macs += size * size * 7 * 7 * 3 * 64
    size = size // 2  # max pool stride 2
    cin = 64
    for stage, (width, blocks) in enumerate(
        ((64, 3), (128, 4), (256, 6), (512, 3))
    ):
        for block in range(blocks):
            stride = 2 if (block == 0 and stage > 0) else 1
            out_size = size // stride
            mid1 = out_size if stride_on == "conv1" else size
            macs += mid1 * mid1 * cin * width  # 1x1
            macs += out_size * out_size * 9 * width * width  # 3x3
            macs += out_size * out_size * width * 4 * width  # 1x1
            if block == 0:
                macs += out_size * out_size * cin * 4 * width  # projection
            cin = 4 * width
            size = out_size
    macs += cin * num_classes
    return macs


def flops_per_item(image_size: int = 224, num_classes: int = 1000,
                   stride_on: str = "conv2") -> float:
    """Forward + backward FLOPs of one image."""
    return 3.0 * 2.0 * forward_macs(image_size, num_classes, stride_on)
