"""Flagship training assembly: transformer × mesh × optimizer → one
jitted SPMD train step with real dp/fsdp/tp/sp/ep shardings.

This is the module the driver's `__graft_entry__.dryrun_multichip`
exercises, and the template for the BERT/Llama-class benchmark configs
(BASELINE.md configs 3-4). Given any `Mesh` built by
`parallel.build_mesh`, it:

  1. adapts the model config to the mesh's live axes,
  2. derives every parameter's PartitionSpec from its logical axes,
  3. initializes global params and places them sharded,
  4. builds the shard_map train step (explicit-collective path).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import dataclasses

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh import EXPERT_AXIS, SEQ_AXIS, TENSOR_AXIS, batch_axes
from ..parallel.sharding import Rules
from ..parallel.train import build_train_step, infer_opt_state_specs
from . import transformer as tfm


def adapt_config(cfg: tfm.TransformerConfig,
                 mesh: Mesh) -> tfm.TransformerConfig:
    """Null out strategy axes the mesh doesn't have (or has at size 1)
    so the model skips dead collectives."""
    def live(name):
        return name if name is not None and mesh.shape.get(name, 1) > 1 \
            else None
    return dataclasses.replace(
        cfg,
        tp_axis=live(cfg.tp_axis),
        sp_axis=live(cfg.sp_axis),
        ep_axis=live(cfg.ep_axis) if cfg.moe else None,
    )


def flagship_param_specs(cfg: tfm.TransformerConfig,
                         mesh: Mesh) -> Dict[str, Any]:
    rules = Rules(tfm.EXTRA_RULES)
    logical = tfm.param_logical_axes(cfg)
    return jax.tree.map(
        lambda ax: rules.spec(ax, mesh), logical,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x))


def batch_spec(mesh: Mesh) -> P:
    baxes = batch_axes(mesh)
    b = baxes if len(baxes) > 1 else (baxes[0] if baxes else None)
    s = SEQ_AXIS if mesh.shape.get(SEQ_AXIS, 1) > 1 else None
    return P(b, s)


def make_flagship(mesh: Mesh,
                  cfg: Optional[tfm.TransformerConfig] = None,
                  optimizer: Optional[optax.GradientTransformation] = None,
                  seed: int = 0,
                  ) -> Tuple[Any, Any, Any, Any]:
    """Returns (cfg, params, opt_state, step) with params/opt_state
    already placed sharded on `mesh` and `step(params, opt_state,
    batch) -> (params, opt_state, metrics)` jitted."""
    cfg = adapt_config(cfg or tfm.TransformerConfig(), mesh)
    optimizer = optimizer or optax.adamw(3e-4)

    tp = mesh.shape.get(TENSOR_AXIS, 1)
    ep = mesh.shape.get(EXPERT_AXIS, 1) if cfg.moe else 1
    params_host = tfm.init_params(cfg, jax.random.PRNGKey(seed),
                                  tp=tp, ep=ep)

    p_specs = flagship_param_specs(cfg, mesh)
    from ..parallel.mesh import FSDP_AXIS
    fsdp_n = mesh.shape.get(FSDP_AXIS, 1)
    if fsdp_n > 1:
        # ZeRO-3 on the explicit path, composable with tp/sp/ep: every
        # parameter's largest unsharded dim shards over fsdp; the
        # train step gathers it back inside the differentiated loss
        # (so the transpose is the gradient reduce-scatter) while the
        # tensor-parallel dims stay sharded for the model's own
        # collectives (parallel/fsdp.py add_fsdp_to_spec).
        from ..parallel.fsdp import add_fsdp_to_spec
        import numpy as _np
        p_specs = jax.tree.map(
            lambda s, p: add_fsdp_to_spec(s, _np.shape(p), fsdp_n),
            p_specs, params_host,
            is_leaf=lambda x: isinstance(x, P))
    p_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs,
                               is_leaf=lambda x: isinstance(x, P))
    params = jax.tree.map(jax.device_put, params_host, p_shardings)

    opt_specs = infer_opt_state_specs(optimizer, params_host, p_specs)
    o_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                               opt_specs,
                               is_leaf=lambda x: isinstance(x, P))
    opt_state = jax.device_put(optimizer.init(params_host), o_shardings)

    def local_loss(params, batch):
        return tfm.loss_fn(cfg, params, batch)

    step = build_train_step(
        local_loss, optimizer, mesh,
        batch_spec=batch_spec(mesh),
        param_specs=p_specs,
        opt_state_specs=opt_specs,
    )
    return cfg, params, opt_state, step


def make_flagship_fsdp(mesh: Mesh,
                       cfg: Optional[tfm.TransformerConfig] = None,
                       optimizer: Optional[
                           optax.GradientTransformation] = None,
                       seed: int = 0,
                       ) -> Tuple[Any, Any, Any, Any]:
    """ZeRO-3 flagship: parameters AND optimizer state sharded over
    the `fsdp` mesh axis, train step built on the constraint-based
    GSPMD path so XLA derives the all-gather(param)/reduce-scatter
    (grad) schedule (see parallel/fsdp.py). The model runs as a
    GLOBAL-array program (strategy axes off) — fsdp composes with
    plain data parallelism, which is its ZeRO semantics; combine with
    tp/sp via the explicit path when model-parallel sharding is also
    needed."""
    from ..parallel.fsdp import zero3_param_shardings
    from ..parallel.train import build_gspmd_train_step

    cfg = dataclasses.replace(cfg or tfm.TransformerConfig(),
                              tp_axis=None, sp_axis=None, ep_axis=None)
    optimizer = optimizer or optax.adamw(3e-4)
    params_host = tfm.init_params(cfg, jax.random.PRNGKey(seed))
    shardings = zero3_param_shardings(params_host, mesh)
    params = jax.tree.map(jax.device_put, params_host, shardings)
    # Optimizer moments are params-shaped and take the SAME ZeRO
    # shardings (explicitly: a jitted optax.init is shape-only, so
    # XLA would constant-fold it onto one device instead of
    # propagating input shardings).
    p_specs = jax.tree.map(lambda s: s.spec, shardings)
    opt_specs = infer_opt_state_specs(optimizer, params_host, p_specs)
    o_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                               opt_specs,
                               is_leaf=lambda x: isinstance(x, P))
    opt_state = jax.tree.map(jax.device_put,
                             optimizer.init(params_host), o_shardings)

    step = build_gspmd_train_step(
        lambda p, b: tfm.loss_fn(cfg, p, b), optimizer, mesh,
        param_shardings=shardings)
    return cfg, params, opt_state, step


def make_batch(cfg: tfm.TransformerConfig, mesh: Mesh,
               global_batch: int, seq_len: int, seed: int = 1
               ) -> Dict[str, jax.Array]:
    """Synthetic token batch, placed with the step's input sharding."""
    key = jax.random.PRNGKey(seed)
    tokens = jax.random.randint(key, (global_batch, seq_len), 0,
                                cfg.vocab, jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    spec = batch_spec(mesh)
    sh = NamedSharding(mesh, spec)
    return {"tokens": jax.device_put(tokens, sh),
            "targets": jax.device_put(targets, sh)}
