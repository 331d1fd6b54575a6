"""ResNet configurations through `horovod_tpu.models.resnet` (the
v1.5 variant: the stride sits on the 3x3 convolution). See
models/transformer.py for what an adapter provides."""

from __future__ import annotations

from types import SimpleNamespace
from typing import Any, Dict, Iterator, Tuple



def conv_layers(config: Dict[str, Any]
                ) -> Iterator[Tuple[int, int, int, int]]:
    """(output side, kernel side, channels in, channels out) of every
    convolution, from the stage sizes, in forward order."""
    side, width = config["image_size"] // 2, config["num_filters"]
    yield side, 7, 3, width
    side //= 2                                  # 3x3 max pool, stride 2
    c_in = width
    for i, blocks in enumerate(config["stage_sizes"]):
        f = width * 2 ** i
        for j in range(blocks):
            stride = 2 if i > 0 and j == 0 else 1
            yield side, 1, c_in, f              # 1x1 at the input side
            side //= stride
            yield side, 3, f, f                 # 3x3 carries the stride
            yield side, 1, f, 4 * f
            if j == 0:
                yield side, 1, c_in, 4 * f      # projection shortcut
            c_in = 4 * f


def flops_per_unit(config: Dict[str, Any], spec: Dict[str, Any]) -> float:
    """Operations the forward and backward passes require for one
    image: 2 per multiply-add of every convolution and of the dense
    layer, times 3 (backward = gradient of the input + of the weight).
    BatchNorm, ReLU and pooling are not counted."""
    forward = sum(2 * side * side * k * k * c_in * c_out
                  for side, k, c_in, c_out in conv_layers(config))
    width = config["num_filters"] * 2 ** (len(config["stage_sizes"]) + 1)
    return 3.0 * (forward + 2 * width * config["num_classes"])


def build(config: Dict[str, Any], spec: Dict[str, Any], n_chips: int):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.models.resnet import ResNet

    side, classes = config["image_size"], config["num_classes"]
    model = ResNet(stage_sizes=list(config["stage_sizes"]),
                   num_classes=classes,
                   num_filters=config["num_filters"], dtype=jnp.bfloat16)

    def init(key):
        variables = model.init(
            key, jnp.zeros((1, side, side, 3), jnp.float32), train=True)
        return variables["params"], variables["batch_stats"]

    def loss_fn(params, batch):
        logits, updates = model.apply(
            {"params": params, "batch_stats": batch["batch_stats"]},
            batch["images"], train=True, mutable=["batch_stats"])
        onehot = jax.nn.one_hot(batch["labels"], classes)
        loss = jnp.mean(
            -jnp.sum(onehot * jax.nn.log_softmax(logits), axis=-1))
        return loss, updates["batch_stats"]

    def make_batch(key, n):
        k1, k2 = jax.random.split(key)
        return {"images": jax.random.normal(k1, (n, side, side, 3),
                                            jnp.float32),
                "labels": jax.random.randint(k2, (n,), 0, classes,
                                             jnp.int32)}

    return SimpleNamespace(
        init=init, loss_fn=loss_fn, has_aux=True,
        carry_key="batch_stats",
        optimizer=optax.sgd(0.0125 * n_chips, momentum=0.9),
        batch_spec={"images": P("data"), "labels": P("data"),
                    "batch_stats": P()},
        make_batch=make_batch,
        sample_batch=lambda key, n: make_batch(
            key, n * spec["sample"]["per_chip"]),
        units_per_sample=1,
        flops_per_unit=flops_per_unit(config, spec),
        step_kwargs={})
