"""Operation and byte counts of the paper's CNN family, from its shapes.

The family: convolutions C128-C64-C128-C256-C512 (3x3, SAME, stride 1), a
ReLU after each, a 2x2 max-pool after every second one while the feature
map is at least 2 wide, a global average pool and a dense head.  Level
``l`` scales every convolution's width by ``base_width * alpha**l``
(``max(4, round(f * s))``); the head stays at ``classes``.

A multiply-add counts as two operations.  Backward is counted as twice the
forward (gradients with respect to activations and to weights), so one
training sample costs three forwards.
"""
from __future__ import annotations


def filters(model: dict, level: int) -> tuple:
    s = model["base_width"] * model["alpha"] ** level
    return tuple(max(4, int(round(f * s))) for f in model["filters"])


def conv_shapes(model: dict, level: int) -> list:
    """(height, width, in_channels, out_channels) of each convolution."""
    h = w = int(model["input_hw"])
    cin = int(model["in_channels"])
    out = []
    for i, f in enumerate(filters(model, level)):
        out.append((h, w, cin, f))
        cin = f
        if i % 2 == 1 and min(h, w) >= 2:
            h, w = h // 2, w // 2
    return out


def forward_flops(model: dict, level: int) -> float:
    """Operations of one sample's forward pass."""
    convs = conv_shapes(model, level)
    total = sum(2.0 * 9 * cin * cout * h * w for h, w, cin, cout in convs)
    return total + 2.0 * convs[-1][3] * model["classes"]


def train_flops(model: dict, level: int) -> float:
    """Operations of one sample's forward and backward pass."""
    return 3.0 * forward_flops(model, level)


def param_count(model: dict, level: int) -> int:
    convs = conv_shapes(model, level)
    n = sum(9 * cin * cout + cout for _, _, cin, cout in convs)
    return n + convs[-1][3] * model["classes"] + model["classes"]
