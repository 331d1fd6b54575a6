"""Device ms a traced step under `hvd.batchnorm`: the BatchNorm work
XLA did not fuse into a convolution's fusion."""

from perfbench.scope_readers import scope_ms

NAME = "batchnorm_ms"
UNIT = "ms"
LAYER = "model layers (models/resnet.py hvd.* scopes)"
MOVES = "images_per_s_chip"


def compute(ctx):
    return scope_ms(ctx, "hvd.batchnorm")
