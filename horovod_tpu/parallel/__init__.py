"""Parallelism layer: meshes, sharding rules, SPMD train steps, and
the full strategy suite (DP/FSDP/TP/SP-ring/SP-Ulysses/EP/PP).

The reference is a data-parallel communication runtime (SURVEY.md
§2.6); this package provides DP at parity and the rest natively, since
named mesh axes + XLA collectives make them first-class on TPU.
"""

from .mesh import (  # noqa: F401
    AXIS_ORDER, DATA_AXIS, EXPERT_AXIS, FSDP_AXIS, PIPE_AXIS, SEQ_AXIS,
    TENSOR_AXIS, MeshSpec, batch_axes, build_mesh, data_parallel_mesh,
    mesh_axis_size,
)
from .sharding import (  # noqa: F401
    DEFAULT_RULES, Rules, replicated, shard_put, tree_shardings,
)
from .train import build_gspmd_train_step, build_train_step  # noqa: F401
from .fsdp import zero3_param_shardings, zero3_spec  # noqa: F401
from .ring_attention import (  # noqa: F401
    attention, dense_attention, ring_attention)
from .ulysses import (  # noqa: F401
    gather_heads, scatter_heads, ulysses_attention,
)
from .moe import moe_ffn, top1_route  # noqa: F401
from .pipeline import pipeline_apply, stack_stage_params  # noqa: F401
