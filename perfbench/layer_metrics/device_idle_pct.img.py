"""See `perfbench/layer_readers.py` `device_idle_pct`."""

from perfbench.layer_readers import device_idle_pct as compute  # noqa: F401

NAME = "device_idle_pct.img"
UNIT = "%"
LAYER = "device"
MOVES = "images_per_s_chip"
