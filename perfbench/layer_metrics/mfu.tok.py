"""See `perfbench/layer_readers.py` `mfu`."""

from perfbench.layer_readers import mfu as compute  # noqa: F401

NAME = "mfu.tok"
UNIT = "%"
LAYER = "XLA fusions (kernels)"
MOVES = "tokens_per_s_chip"
