"""See `perfbench/layer_readers.py` `mfu`."""

from perfbench.layer_readers import mfu as compute  # noqa: F401

NAME = "mfu.img"
UNIT = "%"
LAYER = "XLA fusions (kernels)"
MOVES = "images_per_s_chip"
