"""Operations and bytes counted from shapes: the yardstick of every
utilization and roofline share the benchmark reports.

Counts are of the work the algorithm needs, not of what a kernel happens
to do: padded client slots, recomputation and repeated passes do not
count.
"""
from __future__ import annotations

F32 = 4


def cnn_layers(m: dict) -> list[tuple[str, int, int]]:
    """``(layer, multiply-adds per sample, parameters)`` of the paper's
    CNN (two 3x3 SAME conv + 2x2 max-pool blocks, two dense layers),
    from a configuration's ``model`` sizes."""
    h, w, c = m["height"], m["width"], m["channels"]
    c1, c2, hid, k = m["conv1"], m["conv2"], m["hidden"], m["num_classes"]
    flat = (h // 4) * (w // 4) * c2
    return [
        ("conv1", h * w * 9 * c * c1, 9 * c * c1 + c1),
        ("conv2", (h // 2) * (w // 2) * 9 * c1 * c2, 9 * c1 * c2 + c2),
        ("fc1", flat * hid, flat * hid + hid),
        ("fc2", hid * k, hid * k + k),
    ]


def cnn_params(m: dict) -> int:
    return sum(p for _, _, p in cnn_layers(m))


def cnn_forward_macs(m: dict) -> int:
    """Multiply-adds of one sample's forward pass (convs and dense)."""
    return sum(macs for _, macs, _ in cnn_layers(m))


def cnn_train_flops_per_sample(m: dict) -> int:
    """Forward and backward FLOPs of one sample: 2 per multiply-add
    forward, as much again for the weight gradients, and as much again
    for the input gradients of every layer but the first (the images
    need none). Elementwise work (bias, ReLU, pooling, softmax) is not
    counted."""
    layers = cnn_layers(m)
    macs = sum(x for _, x, _ in layers)
    return 2 * macs + 2 * macs + 2 * (macs - layers[0][1])


def fedavg_agg_quality_cost(k: int, p: int, itemsize: int = F32
                            ) -> tuple[int, int]:
    """``(flops, bytes)`` of one fused aggregation + quality pass over a
    ``(k, p)`` stack of client updates: the stack is read once, the
    aggregate written once; per element one multiply-add for the
    aggregate, one for the dot with it and one for the square norm, plus
    the aggregate's own square norm."""
    flops = 2 * k * p * 3 + 2 * p
    nbytes = k * p * itemsize + p * itemsize + k * F32 + (2 * k + 1) * F32
    return flops, nbytes


def segmented_topk_cost(segments: int, width: int, k: int
                        ) -> tuple[int, int]:
    """``(ops, bytes)`` a per-segment top-``k`` needs: every key read once
    and compared once, and the ``k`` values and indices of each segment
    written once."""
    ops = segments * width
    nbytes = segments * width * F32 + segments * k * 2 * F32
    return ops, nbytes


def roofline_s(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take, and which of its peaks bounds it."""
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
