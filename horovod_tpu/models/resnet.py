"""ResNet family (v1.5) in flax — the benchmark workhorse.

The reference's headline numbers are ResNet-50 synthetic-data
img/sec under data-parallel allreduce (reference:
examples/pytorch/pytorch_synthetic_benchmark.py; docs/benchmarks.rst —
see BASELINE.md). This is the TPU-native equivalent model: NHWC
layout (TPU conv-friendly), bfloat16 compute / float32 BatchNorm
statistics, and optional cross-replica SyncBatchNorm via linen's
`axis_name` (the analog of horovod/torch/sync_batch_norm.py, which
allgathers per-rank mean/var).
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..tracing import device_scope

ModuleDef = Any


class BottleneckBlock(nn.Module):
    filters: int
    strides: Tuple[int, int]
    conv: ModuleDef
    norm: ModuleDef

    @nn.compact
    def __call__(self, x):
        residual = x
        with device_scope("hvd.conv"):
            y = self.conv(self.filters, (1, 1))(x)
        with device_scope("hvd.batchnorm"):
            y = self.norm()(y)
        y = nn.relu(y)
        with device_scope("hvd.conv"):
            y = self.conv(self.filters, (3, 3), self.strides)(y)
        with device_scope("hvd.batchnorm"):
            y = self.norm()(y)
        y = nn.relu(y)
        with device_scope("hvd.conv"):
            y = self.conv(self.filters * 4, (1, 1))(y)
        with device_scope("hvd.batchnorm"):
            y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            with device_scope("hvd.conv"):
                residual = self.conv(
                    self.filters * 4, (1, 1), self.strides,
                    name="conv_proj")(residual)
            with device_scope("hvd.batchnorm"):
                residual = self.norm(name="norm_proj")(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    sync_bn_axes: Optional[Sequence[str]] = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = functools.partial(nn.Conv, use_bias=False,
                                 dtype=self.dtype)
        norm = functools.partial(
            nn.BatchNorm, use_running_average=not train, momentum=0.9,
            epsilon=1e-5, dtype=self.dtype,
            axis_name=(tuple(self.sync_bn_axes)
                       if self.sync_bn_axes else None))
        x = x.astype(self.dtype)
        with device_scope("hvd.conv"):
            x = conv(self.num_filters, (7, 7), (2, 2),
                     padding=[(3, 3), (3, 3)], name="conv_init")(x)
        with device_scope("hvd.batchnorm"):
            x = norm(name="bn_init")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = BottleneckBlock(self.num_filters * 2 ** i, strides,
                                    conv, norm)(x)
        with device_scope("hvd.head_loss"):
            x = jnp.mean(x, axis=(1, 2))
            x = nn.Dense(self.num_classes, dtype=jnp.float32)(x)
            return x.astype(jnp.float32)


ResNet50 = functools.partial(ResNet, stage_sizes=[3, 4, 6, 3])
ResNet101 = functools.partial(ResNet, stage_sizes=[3, 4, 23, 3])
ResNet152 = functools.partial(ResNet, stage_sizes=[3, 8, 36, 3])


def create_resnet50(num_classes: int = 1000,
                    sync_bn_axes: Optional[Sequence[str]] = None,
                    dtype=jnp.bfloat16) -> ResNet:
    return ResNet50(num_classes=num_classes, sync_bn_axes=sync_bn_axes,
                    dtype=dtype)


def init_resnet(model: ResNet, key: jax.Array,
                image_size: int = 224) -> Any:
    """Returns {'params': ..., 'batch_stats': ...}."""
    dummy = jnp.zeros((1, image_size, image_size, 3), jnp.float32)
    return model.init(key, dummy, train=True)


def resnet_loss_fn(model: ResNet, variables, batch, train: bool = True):
    """Softmax cross-entropy; returns (loss, new_batch_stats)."""
    images, labels = batch["images"], batch["labels"]
    if train:
        logits, updates = model.apply(
            variables, images, train=True, mutable=["batch_stats"])
        new_stats = updates["batch_stats"]
    else:
        logits = model.apply(variables, images, train=False)
        new_stats = variables.get("batch_stats")
    with device_scope("hvd.head_loss"):
        onehot = jax.nn.one_hot(labels, logits.shape[-1])
        loss = jnp.mean(
            -jnp.sum(onehot * jax.nn.log_softmax(logits), axis=-1))
    return loss, new_stats
