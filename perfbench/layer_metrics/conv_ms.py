"""Device ms a traced step under `hvd.conv`. XLA names a fusion after
its convolution, so what it fused into one (BatchNorm passes, the
SGD update) counts here."""

from perfbench.scope_readers import scope_ms

NAME = "conv_ms"
UNIT = "ms"
LAYER = "model layers (models/resnet.py hvd.* scopes)"
MOVES = "images_per_s_chip"


def compute(ctx):
    return scope_ms(ctx, "hvd.conv")
