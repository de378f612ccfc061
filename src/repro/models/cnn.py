"""The paper's experiment model: a small CNN classifier for MNIST-like
(1x28x28) and CIFAR-like (3x32x32) data (paper §VIII), in pure JAX.

Mirrors the reference repo the paper builds on [14] (two conv blocks +
two dense layers).

``forward``/``loss_fn`` accept an ``impl`` knob selecting the lowering:

- ``"reference"`` (default): ``lax.conv_general_dilated`` +
  ``lax.reduce_window`` max-pool — the original formulation.
- ``"fast"``: identical math, CPU-friendly lowering — the first conv
  (few input channels) via im2col patches + matmul and 2x2 max-pool via
  a reshape + max. Forward outputs agree with "reference" to f32
  rounding (the GEMM sums the conv taps in another order than XLA's
  conv; about 1e-6 on logits of magnitude 1); gradients agree up to
  max-pool tie-breaking and f32 reduction order.
  On XLA CPU the backward pass avoids SelectAndScatter, which dominates
  the reference formulation's round time (~3x faster grads).
- ``"auto"``: "fast" off-TPU, "reference" on TPU (where the native
  conv/reduce_window path is the tuned one).

The device-resident FL data plane (fl.round.make_fl_rounds_scan) trains
with ``impl="auto"``; everything else keeps the reference lowering.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class CNNConfig:
    name: str = "cnn-mnist"
    height: int = 28
    width: int = 28
    channels: int = 1
    num_classes: int = 10
    conv1: int = 16
    conv2: int = 32
    hidden: int = 128
    dtype: str = "float32"


MNIST_CNN = CNNConfig()
CIFAR_CNN = CNNConfig(name="cnn-cifar", height=32, width=32, channels=3,
                      conv1=32, conv2=64, hidden=256)


def init_params(cfg: CNNConfig, key):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    h2, w2 = cfg.height // 4, cfg.width // 4         # two 2x2 maxpools
    flat = h2 * w2 * cfg.conv2

    def conv_init(k, shape):  # HWIO
        fan_in = shape[0] * shape[1] * shape[2]
        return (jax.random.normal(k, shape) * (2.0 / fan_in) ** 0.5).astype(dt)

    return {
        "conv1": {"w": conv_init(k1, (3, 3, cfg.channels, cfg.conv1)),
                  "b": jnp.zeros(cfg.conv1, dt)},
        "conv2": {"w": conv_init(k2, (3, 3, cfg.conv1, cfg.conv2)),
                  "b": jnp.zeros(cfg.conv2, dt)},
        "fc1": {"w": (jax.random.normal(k3, (flat, cfg.hidden)) * flat ** -0.5).astype(dt),
                "b": jnp.zeros(cfg.hidden, dt)},
        "fc2": {"w": (jax.random.normal(k4, (cfg.hidden, cfg.num_classes))
                      * cfg.hidden ** -0.5).astype(dt),
                "b": jnp.zeros(cfg.num_classes, dt)},
    }


def _conv_direct(x, w):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _conv_im2col(x, w):
    """3x3 SAME conv as 9 shifted slices + one matmul (im2col).

    Equal to :func:`_conv_direct` up to f32 summation order; much
    faster on XLA CPU when the input channel count is small (the GEMM
    replaces a skinny conv).
    """
    B, H, W, Cin = x.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    cols = [xp[:, i:i + H, j:j + W, :] for i in range(3) for j in range(3)]
    patches = jnp.concatenate(cols, axis=-1)            # (B,H,W,9*Cin)
    out = patches.reshape(B * H * W, 9 * Cin) @ w.reshape(9 * Cin, -1)
    return out.reshape(B, H, W, -1)


def _pool_window(y):
    return jax.lax.reduce_window(y, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


def _pool_reshape(y):
    """2x2 max-pool via reshape+max: same forward values as
    ``reduce_window`` (odd trailing rows/cols dropped, matching VALID
    windows); its VJP avoids XLA's SelectAndScatter (the CPU bottleneck
    of the reference formulation's backward pass)."""
    B, H, W, C = y.shape
    y = y[:, :H - H % 2, :W - W % 2, :]
    return y.reshape(B, H // 2, 2, W // 2, 2, C).max(axis=(2, 4))


def _resolve_impl(impl: str) -> str:
    if impl == "auto":
        return "reference" if jax.default_backend() == "tpu" else "fast"
    if impl not in ("reference", "fast"):
        raise ValueError(f"unknown cnn impl {impl!r}")
    return impl


def _conv_block(x, p, impl: str = "reference"):
    conv = _conv_im2col if impl == "fast" else _conv_direct
    pool = _pool_reshape if impl == "fast" else _pool_window
    y = jax.nn.relu(conv(x, p["w"]) + p["b"])
    return pool(y)


def forward(cfg: CNNConfig, params, images, impl: str = "reference"):
    """images: (B, H, W, C) -> logits (B, num_classes)."""
    impl = _resolve_impl(impl)
    x = _conv_block(images, params["conv1"], impl)
    x = _conv_block(x, params["conv2"], impl)
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["fc1"]["w"] + params["fc1"]["b"])
    return x @ params["fc2"]["w"] + params["fc2"]["b"]


def loss_fn(cfg: CNNConfig, params, batch, impl: str = "reference"):
    """batch: images (B,H,W,C), labels (B,), weights optional (B,)."""
    logits = forward(cfg, params, batch["images"], impl=impl)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    nll = -jnp.take_along_axis(logp, batch["labels"][:, None], axis=1)[:, 0]
    w = batch.get("weights")
    loss = nll.mean() if w is None else jnp.sum(nll * w) / jnp.maximum(w.sum(), 1e-9)
    acc = (logits.argmax(-1) == batch["labels"]).mean()
    return loss, {"loss": loss, "accuracy": acc}
