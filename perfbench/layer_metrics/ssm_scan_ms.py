"""Device ms a traced step under `hvd.ssm.scan`: the Mamba mixers'
selective scan (`parallel/selective_scan.py`: on the TPU the kernels
`hvd_selective_scan_fwd` / `_bwd`, the state carried through the
positions in VMEM, and the D skip and silu(z) gate beside them), all
passes. Nothing where the program has no such scope."""

from perfbench.scope_readers import scope_ms

NAME = "ssm_scan_ms"
UNIT = "ms"
LAYER = "model layers (hvd.* scopes)"
MOVES = "tokens_per_s_chip"


def compute(ctx):
    return scope_ms(ctx, "hvd.ssm.scan")
