"""Device ms a traced step under `hvd.optimizer`."""

from perfbench.scope_readers import scope_ms

NAME = "optimizer_ms.tok"
UNIT = "ms"
LAYER = "optimizer update (parallel/train.py _finish_step)"
MOVES = "tokens_per_s_chip"


def compute(ctx):
    return scope_ms(ctx, "hvd.optimizer")
