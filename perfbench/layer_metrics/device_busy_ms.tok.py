"""See `perfbench/layer_readers.py` `device_busy_ms`."""

from perfbench.layer_readers import device_busy_ms as compute  # noqa: F401

NAME = "device_busy_ms.tok"
UNIT = "ms"
LAYER = "jit step, device side (parallel/train.py build_train_step)"
MOVES = "tokens_per_s_chip"
