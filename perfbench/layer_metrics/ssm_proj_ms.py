"""Device ms a traced step under `hvd.ssm.proj`: a Mamba mixer around
its scan (`models/jamba.py`: the input norm, in_proj, the causal conv,
x_proj, the dt / B / C norms, dt_proj and softplus, out_proj and the
residual add), all passes. Nothing where the program has no such
scope."""

from perfbench.scope_readers import scope_ms

NAME = "ssm_proj_ms"
UNIT = "ms"
LAYER = "model layers (hvd.* scopes)"
MOVES = "tokens_per_s_chip"


def compute(ctx):
    return scope_ms(ctx, "hvd.ssm.proj")
