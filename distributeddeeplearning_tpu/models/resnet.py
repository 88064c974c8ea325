"""ResNet v1 family in Flax — TPU-first re-design of the reference builder.

Capability parity with ``HorovodTF/src/resnet_model.py`` (320 LoC,
graph-mode TF): ResNet v1 depths {18, 34, 50, 101, 152, 200} with the
depth→layers table (``resnet_model.py:306-313``), BN momentum 0.9 / eps
1e-5 (``:10-11``), zero-initialised gamma on the last BN of every residual
branch (``:150, :201``), and input-size-independent "fixed" padding before
strided convs (``fixed_padding`` ``:56-81``). Also covers the stock
ResNet50s the Keras/PyTorch paths pull from their libraries
(``imagenet_keras_horovod.py:101``, ``imagenet_pytorch_horovod.py:323``).

TPU-first choices (not in the reference):
* **NHWC** (channels-last) — XLA:TPU's native conv layout; the reference
  uses NCHW for cuDNN.
* **bfloat16 compute / float32 params & BN stats** — keeps the MXU fed at
  its native dtype while accumulating statistics in f32. Logits are cast
  to f32 before the loss.
* Static shapes and compact modules — the whole forward pass traces to a
  single XLA computation; BN+ReLU fuse into the preceding conv.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp

ModuleDef = Any

# Depth → (block kind, stage sizes). Reference table resnet_model.py:306-313.
_STAGES = {
    18: ("basic", (2, 2, 2, 2)),
    34: ("basic", (3, 4, 6, 3)),
    50: ("bottleneck", (3, 4, 6, 3)),
    101: ("bottleneck", (3, 4, 23, 3)),
    152: ("bottleneck", (3, 8, 36, 3)),
    200: ("bottleneck", (3, 24, 36, 3)),
}

_KERNEL_INIT = nn.initializers.variance_scaling(2.0, "fan_out", "truncated_normal")
_BN_EPS = 1e-5  # reference constant (resnet_model.py:10-11); ONE copy


def _conv(
    filters: int,
    kernel: int,
    strides: int,
    dtype,
    name: str = None,
) -> nn.Conv:
    """Conv with reference "fixed padding" semantics (resnet_model.py:56-109):
    explicit symmetric padding for strided convs so output size is
    input-size-independent; SAME otherwise. Bias-free (BN follows)."""
    if strides > 1:
        pad = (kernel - 1) // 2
        padding = [(pad, pad), (pad, pad)]
    else:
        padding = "SAME"
    return nn.Conv(
        filters,
        (kernel, kernel),
        strides=(strides, strides),
        padding=padding,
        use_bias=False,
        dtype=dtype,
        param_dtype=jnp.float32,
        kernel_init=_KERNEL_INIT,
        name=name,
    )


def _batch_norm(
    train: bool,
    dtype,
    zero_init: bool = False,
    name: str = None,
):
    """BN with reference constants: momentum .9, eps 1e-5
    (resnet_model.py:10-11); optionally zero-init gamma (:150, :201).
    The batch statistics reduce in float32 (flax's default).

    Uses the per-replica-capable subclass (``models/norm.py``): the
    pjit engine's batch-split grouping engages through it, the dp
    engine sees plain ``nn.BatchNorm`` behavior.
    """
    from distributeddeeplearning_tpu.models.norm import BatchNorm

    return BatchNorm(
        use_running_average=not train,
        momentum=0.9,
        epsilon=_BN_EPS,
        dtype=dtype,
        param_dtype=jnp.float32,
        scale_init=nn.initializers.zeros if zero_init else nn.initializers.ones,
        name=name,
    )


class BasicBlock(nn.Module):
    """Two 3×3 convs (reference ``residual_block`` :112-153)."""

    filters: int
    strides: int = 1
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = True):
        bn = functools.partial(_batch_norm, train, self.dtype)
        residual = x
        y = _conv(self.filters, 3, self.strides, self.dtype)(x)
        y = bn()(y)
        y = nn.relu(y)
        y = _conv(self.filters, 3, 1, self.dtype)(y)
        y = bn(zero_init=True)(y)
        if residual.shape != y.shape:
            residual = _conv(self.filters, 1, self.strides, self.dtype, name="proj_conv")(x)
            residual = bn(name="proj_bn")(residual)
        return nn.relu(y + residual)


class BottleneckBlock(nn.Module):
    """1×1 → 3×3 → 1×1(×4) (reference ``bottleneck_block`` :156-204)."""

    filters: int
    strides: int = 1
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = True):
        bn = functools.partial(_batch_norm, train, self.dtype)
        residual = x
        y = _conv(self.filters, 1, 1, self.dtype)(x)
        y = bn()(y)
        y = nn.relu(y)
        y = _conv(self.filters, 3, self.strides, self.dtype)(y)
        y = bn()(y)
        y = nn.relu(y)
        y = _conv(4 * self.filters, 1, 1, self.dtype)(y)
        y = bn(zero_init=True)(y)
        if residual.shape != y.shape:
            residual = _conv(4 * self.filters, 1, self.strides, self.dtype, name="proj_conv")(x)
            residual = bn(name="proj_bn")(residual)
        return nn.relu(y + residual)


class ResNet(nn.Module):
    """ResNet v1 (reference ``resnet_v1_generator`` :237-301).

    Stem: 7×7/2 conv(64) → BN → ReLU → 3×3/2 maxpool; four stages with
    filters (64, 128, 256, 512) and strides (1, 2, 2, 2); global average
    pool; dense head. Returns float32 logits.
    """

    depth: int = 50
    num_classes: int = 1000
    dtype: Any = jnp.bfloat16

    @property
    def per_replica_bn_capable(self) -> bool:
        """Every BN is the group-capable subclass (models/norm.py): the
        pjit engine's batch-split per-replica BN applies."""
        return True

    @nn.compact
    def __call__(self, x, train: bool = True):
        if self.depth not in _STAGES:
            raise ValueError(
                f"depth must be one of {sorted(_STAGES)}, got {self.depth}"
            )  # reference raises the same way, resnet_model.py:314-317
        kind, stage_sizes = _STAGES[self.depth]
        block = BasicBlock if kind == "basic" else BottleneckBlock

        x = jnp.asarray(x, self.dtype)
        x = _conv(64, 7, 2, self.dtype, name="stem_conv")(x)
        x = _batch_norm(train, self.dtype, name="stem_bn")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")

        for stage, n_blocks in enumerate(stage_sizes):
            for b in range(n_blocks):
                strides = 2 if (stage > 0 and b == 0) else 1
                x = block(
                    filters=64 * 2**stage,
                    strides=strides,
                    dtype=self.dtype,
                    name=f"stage{stage + 1}_block{b + 1}",
                )(x, train=train)

        x = jnp.mean(x, axis=(1, 2))  # global average pool
        x = nn.Dense(
            self.num_classes,
            dtype=self.dtype,
            param_dtype=jnp.float32,
            name="head",
        )(x)
        return jnp.asarray(x, jnp.float32)


def resnet_v1(depth: int, num_classes: int = 1000, dtype=jnp.bfloat16) -> ResNet:
    """Factory matching the reference entry point ``resnet_v1(resnet_depth,
    num_classes, data_format)`` (``resnet_model.py:304-320``); data_format is
    fixed to NHWC (TPU-native) by design."""
    return ResNet(depth=depth, num_classes=num_classes, dtype=dtype)


ResNet18 = functools.partial(ResNet, depth=18)
ResNet34 = functools.partial(ResNet, depth=34)
ResNet50 = functools.partial(ResNet, depth=50)
ResNet101 = functools.partial(ResNet, depth=101)
ResNet152 = functools.partial(ResNet, depth=152)
ResNet200 = functools.partial(ResNet, depth=200)
