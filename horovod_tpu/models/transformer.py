"""Flagship model: Llama-style decoder transformer with explicit
TPU-native parallelism (TP / SP-ring / EP / PP) via manual collectives
inside `shard_map`.

The reference has no model code at all — it moves gradient bytes
(SURVEY.md §5.7). This model family is the proof that the framework's
collective layer supports the full parallelism suite the task brief
demands, and it is the vehicle for the BERT/Llama-class benchmark
configs (BASELINE.md configs 3 & 4):

  * Tensor parallel: Megatron-style — attention heads and MLP hidden
    sharded over `tensor`; one psum after the attention out-projection,
    one after the MLP down-projection.
  * Sequence parallel: ring attention over `seq` (ppermute ring, exact
    blockwise softmax) — long-context first-class.
  * Expert parallel: Switch-style MoE FFN with all_to_all token
    routing over `expert`.
  * Vocab parallel: embedding + LM head sharded over `tensor`, with a
    psum'd one-hot lookup and a vocab-parallel cross-entropy
    (pmax/psum log-sum-exp) so full logits never materialize.

Everything is bfloat16 matmul / float32 accumulate, static shapes,
`lax.scan` over stacked layer weights — MXU- and XLA-friendly by
construction.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.mesh import EXPERT_AXIS, SEQ_AXIS, TENSOR_AXIS
from ..parallel.ring_attention import attention as full_attention
from ..parallel.ring_attention import ring_attention
from ..tracing import device_scope


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8
    head_dim: int = 64
    d_ff: int = 1376
    max_seq: int = 2048
    moe: bool = False
    n_experts: int = 8
    capacity_factor: float = 1.25
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    # Rematerialize each layer in backward (jax.checkpoint on the scan
    # body): activation memory drops from O(n_layers x per-layer
    # temps) to O(n_layers x residual) + one layer's temps, trading
    # ~33% more FLOPs — the standard TPU HBM/FLOPs trade for training
    # large configs on a 16GB chip.
    remat: bool = False
    # remat_mode="full": the whole layer recomputes in backward.
    # "mlp_only": only the FFN sub-block remats (its d_ff temporaries
    # are the memory hog; its recompute is cheap dots) while the
    # attention sub-block SAVES its residuals, so the fused attention
    # kernel's forward does not run again inside backward (round 5
    # built it for JAX's stock flash kernel at 128-blocks on the
    # flagship model, where it did not rescue that kernel; no cell
    # runs it, see PERF.md PR 30). Costs ~4x B*L*D extra bytes per
    # layer.
    remat_mode: str = "full"
    # Live mesh axis names (None → that strategy is off). The model is
    # written once; trivial axes cost nothing.
    tp_axis: Optional[str] = TENSOR_AXIS
    sp_axis: Optional[str] = SEQ_AXIS
    ep_axis: Optional[str] = EXPERT_AXIS

    def tp(self) -> int:
        return _axis_size(self.tp_axis)

    def sp(self) -> int:
        return _axis_size(self.sp_axis)


def _axis_size(name: Optional[str]) -> int:
    if name is None:
        return 1
    try:
        # hvdlint: disable-next=HVD005 (not rank divergence:
        # NameError means the axis is unbound — outside shard_map —
        # which is identical on every rank tracing the same program)
        return lax.axis_size(name)
    except NameError:
        return 1


def _maybe_psum(x, name: Optional[str]):
    return lax.psum(x, name) if name is not None and _axis_size(name) > 1 \
        else x


def _maybe_pmax(x, name: Optional[str]):
    return lax.pmax(x, name) if name is not None and _axis_size(name) > 1 \
        else x


def _axis_index(name: Optional[str]) -> jax.Array:
    if name is None:
        return jnp.zeros((), jnp.int32)
    try:
        return lax.axis_index(name)
    except NameError:
        return jnp.zeros((), jnp.int32)


# ---------------------------------------------------------------------------
# Parameter init + logical sharding axes
# ---------------------------------------------------------------------------

def init_params(cfg: TransformerConfig, key: jax.Array,
                tp: int = 1, ep: int = 1) -> Dict[str, Any]:
    """Init GLOBAL (unsharded) parameters; stacked over layers for
    lax.scan. tp/ep are used only for divisibility checks."""
    assert cfg.n_heads % tp == 0 and cfg.n_kv_heads % tp == 0
    assert cfg.d_ff % tp == 0 and cfg.vocab % tp == 0
    if cfg.moe:
        assert cfg.n_experts % ep == 0
    D, H, KV, Dh, F, L = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                          cfg.head_dim, cfg.d_ff, cfg.n_layers)
    k = iter(jax.random.split(key, 16))
    dt = cfg.dtype

    def norm_init(*shape):
        return jnp.ones(shape, jnp.float32)

    def dense_init(kk, *shape, scale=None):
        fan_in = shape[-2] if len(shape) >= 2 else shape[0]
        s = scale if scale is not None else fan_in ** -0.5
        return (jax.random.normal(kk, shape, jnp.float32) * s).astype(dt)

    params = {
        "embed": dense_init(next(k), cfg.vocab, D, scale=1.0),
        "final_norm": norm_init(D),
        "layers": {
            "attn_norm": norm_init(L, D),
            "mlp_norm": norm_init(L, D),
            "wq": dense_init(next(k), L, D, H * Dh),
            "wk": dense_init(next(k), L, D, KV * Dh),
            "wv": dense_init(next(k), L, D, KV * Dh),
            "wo": dense_init(next(k), L, H * Dh, D),
        },
    }
    if cfg.moe:
        E = cfg.n_experts
        params["layers"].update({
            "router": dense_init(next(k), L, D, E).astype(jnp.float32),
            "w_gate": dense_init(next(k), L, E, D, F),
            "w_up": dense_init(next(k), L, E, D, F),
            "w_down": dense_init(next(k), L, E, F, D),
        })
    else:
        params["layers"].update({
            "w_gate": dense_init(next(k), L, D, F),
            "w_up": dense_init(next(k), L, D, F),
            "w_down": dense_init(next(k), L, F, D),
        })
    return params


def param_logical_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    """Logical axis names per parameter (layer-stacked leading dim is
    None = replicated stacking dim; pipeline sharding of it is applied
    by the caller when pp>1)."""
    base = {
        "embed": ("vocab", "embed_tail"),
        "final_norm": (None,),
        "layers": {
            "attn_norm": (None, None),
            "mlp_norm": (None, None),
            "wq": (None, None, "heads_flat"),
            "wk": (None, None, "heads_flat"),
            "wv": (None, None, "heads_flat"),
            "wo": (None, "heads_flat", None),
        },
    }
    if cfg.moe:
        base["layers"].update({
            "router": (None, None, None),
            "w_gate": (None, "expert", None, "mlp"),
            "w_up": (None, "expert", None, "mlp"),
            "w_down": (None, "expert", "mlp", None),
        })
    else:
        base["layers"].update({
            "w_gate": (None, None, "mlp"),
            "w_up": (None, None, "mlp"),
            "w_down": (None, "mlp", None),
        })
    return base


# Extra logical names used above → mesh axes (extends DEFAULT_RULES).
EXTRA_RULES = {
    "heads_flat": TENSOR_AXIS,   # flattened (heads*head_dim) columns
    "embed_tail": None,
    "mlp": TENSOR_AXIS,
    "vocab": TENSOR_AXIS,
    "expert": EXPERT_AXIS,
}


# ---------------------------------------------------------------------------
# Building blocks (all operate on LOCAL shards inside shard_map)
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale * w).astype(x.dtype)


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, L, H, Dh); positions: (L,) global positions."""
    Dh = x.shape[-1]
    half = Dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freqs[None, :]  # (L,half)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin],
        axis=-1).astype(x.dtype)


def _attention_block(cfg: TransformerConfig, p: Dict[str, jax.Array],
                     x: jax.Array) -> jax.Array:
    """x: (B, L_local, D). Heads already sharded over tp (weights are
    local shards: wq (D, H_local*Dh) etc.)."""
    B, L, D = x.shape
    Dh = cfg.head_dim
    with device_scope("hvd.attn.proj"):
        h = rmsnorm(x, p["attn_norm"])
        q = (h @ p["wq"]).reshape(B, L, -1, Dh)
        kk = (h @ p["wk"]).reshape(B, L, -1, Dh)
        v = (h @ p["wv"]).reshape(B, L, -1, Dh)

        sp_idx = _axis_index(cfg.sp_axis)
        positions = sp_idx * L + jnp.arange(L)
        q = _rope(q, positions, cfg.rope_theta)
        kk = _rope(kk, positions, cfg.rope_theta)

    with device_scope("hvd.attn.core"):
        if cfg.sp_axis is not None and _axis_size(cfg.sp_axis) > 1:
            # GQA: the ring takes kv heads repeated to the local q
            # heads; full_attention takes them as they are.
            reps = q.shape[2] // kk.shape[2]
            if reps > 1:
                kk = jnp.repeat(kk, reps, axis=2)
                v = jnp.repeat(v, reps, axis=2)
            o = ring_attention(q, kk, v, cfg.sp_axis, causal=True)
        else:
            o = full_attention(q, kk, v, causal=True)

    with device_scope("hvd.attn.proj"):
        o = o.reshape(B, L, -1) @ p["wo"]      # partial sum over tp shard
        o = _maybe_psum(o, cfg.tp_axis)
        return x + o.astype(x.dtype)


def _dense_ffn(cfg: TransformerConfig, p, x):
    with device_scope("hvd.ffn"):
        h = rmsnorm(x, p["mlp_norm"])
        gate = jax.nn.silu((h @ p["w_gate"]).astype(jnp.float32))
        up = (h @ p["w_up"]).astype(jnp.float32)
        out = (gate * up).astype(x.dtype) @ p["w_down"]
        out = _maybe_psum(out, cfg.tp_axis)
        return x + out.astype(x.dtype)


def _ffn_block(cfg: TransformerConfig, p: Dict[str, jax.Array],
               x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    if cfg.moe:
        # fold gate/up into one in-projection for the shared moe_ffn
        # (SwiGLU needs two; combine by concat on F).
        pm = dict(p)
        with device_scope("hvd.moe"):
            pm["w_gate_combined"] = jnp.concatenate(
                [p["w_gate"], p["w_up"]], axis=-1)
        # hvdlint: disable-next=HVD005 (branch on static model
        # config: cfg.moe is identical on every rank, each arm is a
        # uniform schedule)
        return _moe_swiglu(cfg, pm, x)
    # hvdlint: disable-next=HVD005 (same static-config branch)
    return _dense_ffn(cfg, p, x), jnp.zeros((), jnp.float32)


def _layer(cfg: TransformerConfig, p: Dict[str, jax.Array],
           x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    x = _attention_block(cfg, p, x)
    return _ffn_block(cfg, p, x)


def _moe_swiglu(cfg: TransformerConfig, p, x):
    """MoE FFN with SwiGLU experts: in-proj produces [gate|up] (2F),
    activation splits them."""
    from ..parallel.moe import top1_route
    with device_scope("hvd.moe"):
        B, L, D = x.shape
        h = rmsnorm(x, p["mlp_norm"])
        tokens = h.reshape(B * L, D).astype(jnp.float32)
        ep_axis = (cfg.ep_axis if cfg.ep_axis is not None and
                   _axis_size(cfg.ep_axis) > 1 else None)
        ep = _axis_size(ep_axis) if ep_axis else 1
        E_local = p["w_down"].shape[0]
        E = E_local * ep
        T = tokens.shape[0]
        C = max(1, int(cfg.capacity_factor * T / E))

        logits = tokens @ p["router"]
        dispatch, combine, aux = top1_route(logits, E, C)
        xs = jnp.einsum("tec,td->ecd", dispatch, tokens)
        if ep_axis:
            xs = xs.reshape(ep, E_local, C, D)
            xs = lax.all_to_all(xs, ep_axis, split_axis=0, concat_axis=2,
                                tiled=True)
            xs = xs.reshape(E_local, ep * C, D)
        else:
            xs = xs.reshape(E_local, C, D)
        win = p["w_gate_combined"].astype(jnp.float32)   # (E_local, D, 2F)
        F = win.shape[-1] // 2
        hh = jnp.einsum("ecd,edf->ecf", xs, win)
        act = jax.nn.silu(hh[..., :F]) * hh[..., F:]
        ys = jnp.einsum("ecf,efd->ecd", act,
                        p["w_down"].astype(jnp.float32))
        if ep_axis:
            ys = ys.reshape(E_local, ep, C, D)
            ys = lax.all_to_all(ys, ep_axis, split_axis=1, concat_axis=0,
                                tiled=True)
            ys = ys.reshape(E, C, D)
        out = jnp.einsum("tec,ecd->td", combine, ys)
        # expert hidden F is tp-sharded too: the down-projection contracted
        # a sharded dim, so this is a partial sum until psum over tensor.
        out = _maybe_psum(out, cfg.tp_axis)
        return x + out.reshape(B, L, D).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Forward + loss
# ---------------------------------------------------------------------------

def embed_lookup(cfg: TransformerConfig, embed: jax.Array,
                 tokens: jax.Array) -> jax.Array:
    """Vocab-parallel embedding: `embed` is the LOCAL (V_local, D)
    shard; tokens are global ids."""
    tp = _axis_size(cfg.tp_axis)
    V_local = embed.shape[0]
    if tp == 1:
        # hvdlint: disable-next=HVD005 (tp is a trace-time mesh
        # constant, identical on every rank of the same program)
        return embed[tokens]
    shard = _axis_index(cfg.tp_axis)
    lo = shard * V_local
    local_ids = jnp.clip(tokens - lo, 0, V_local - 1)
    mine = (tokens >= lo) & (tokens < lo + V_local)
    out = jnp.where(mine[..., None], embed[local_ids],
                    jnp.zeros((), embed.dtype))
    return _maybe_psum(out.astype(jnp.float32),
                       cfg.tp_axis).astype(embed.dtype)


def vocab_parallel_xent(cfg: TransformerConfig, logits: jax.Array,
                        targets: jax.Array) -> jax.Array:
    """Cross-entropy over vocab-sharded logits (T, V_local) without
    materializing full logits: global log-sum-exp via pmax+psum and a
    masked gather of the target logit."""
    tp = _axis_size(cfg.tp_axis)
    lf = logits.astype(jnp.float32)
    if tp == 1:
        lse = jax.scipy.special.logsumexp(lf, axis=-1)
        tgt = jnp.take_along_axis(lf, targets[..., None],
                                  axis=-1)[..., 0]
        # hvdlint: disable-next=HVD005 (tp is a trace-time mesh
        # constant, identical on every rank of the same program)
        return lse - tgt
    V_local = lf.shape[-1]
    shard = _axis_index(cfg.tp_axis)
    lo = shard * V_local
    # stop_gradient BEFORE the pmax: the stabilizing max cancels in
    # d(lse)/d(logits), and pmax has no VJP rule — keep the whole max
    # chain out of the differentiated graph.
    gmax = _maybe_pmax(jnp.max(lax.stop_gradient(lf), axis=-1),
                       cfg.tp_axis)
    sumexp = jnp.sum(jnp.exp(lf - gmax[..., None]), axis=-1)
    sumexp = _maybe_psum(sumexp, cfg.tp_axis)
    lse = jnp.log(sumexp) + gmax
    local_ids = jnp.clip(targets - lo, 0, V_local - 1)
    mine = (targets >= lo) & (targets < lo + V_local)
    tgt_local = jnp.take_along_axis(lf, local_ids[..., None],
                                    axis=-1)[..., 0]
    tgt = _maybe_psum(jnp.where(mine, tgt_local, 0.0), cfg.tp_axis)
    return lse - tgt


def forward(cfg: TransformerConfig, params: Dict[str, Any],
            tokens: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """tokens: (B, L_local) → hidden states (B, L_local, D) and
    summed MoE aux loss. Operates on LOCAL param shards."""
    with device_scope("hvd.embed"):
        x = embed_lookup(cfg, params["embed"], tokens)

    def one_layer(layer_p, x):
        return _layer(cfg, layer_p, x)

    if cfg.remat:
        if cfg.remat_mode not in ("full", "mlp_only"):
            raise ValueError(
                f"remat_mode must be 'full' or 'mlp_only', got "
                f"{cfg.remat_mode!r}")
        if cfg.remat_mode == "mlp_only":
            # Attention residuals saved (flash's custom-VJP forward
            # never re-runs); only the FFN recomputes.
            ffn = jax.checkpoint(
                lambda layer_p, x: _ffn_block(cfg, layer_p, x))

            def one_layer(layer_p, x):  # noqa: F811
                x = _attention_block(cfg, layer_p, x)
                return ffn(layer_p, x)
        else:
            one_layer = jax.checkpoint(one_layer)

    def body(carry, layer_p):
        x, aux = carry
        x, a = one_layer(layer_p, x)
        return (x, aux + a), None

    # The scan carry must have the per-layer aux's shard_map
    # varying-axes type on every live mesh axis (the MoE aux also
    # varies over `tensor`, which x alone does not).
    aux_t = jax.eval_shape(
        one_layer, jax.tree.map(lambda p: p[0], params["layers"]), x)[1]
    aux0 = jnp.zeros((), jnp.float32)
    if aux_t.vma:
        aux0 = lax.pcast(aux0, tuple(aux_t.vma), to="varying")
    (x, aux), _ = lax.scan(body, (x, aux0), params["layers"])
    with device_scope("hvd.head_loss"):
        x = rmsnorm(x, params["final_norm"])
    return x, aux


def logits_fn(cfg: TransformerConfig, params, hidden) -> jax.Array:
    """LM head, tied to the (vocab-sharded) embedding: (B, L, V_local).
    The matmul runs at the model's compute dtype (bf16 = MXU full
    rate) with an f32 accumulator/output — the xent's LSE math needs
    f32 logits, not an f32-rate matmul."""
    return jnp.einsum("bld,vd->blv", hidden.astype(cfg.dtype),
                      params["embed"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def loss_fn(cfg: TransformerConfig, params, batch) -> jax.Array:
    """Next-token loss, local mean. batch: dict(tokens (B, L_local),
    targets (B, L_local)); caller pmeans over batch/seq axes."""
    hidden, aux = forward(cfg, params, batch["tokens"])
    with device_scope("hvd.head_loss"):
        logits = logits_fn(cfg, params, hidden)
        nll = vocab_parallel_xent(cfg, logits, batch["targets"])
        loss = jnp.mean(nll) + 0.01 * aux
    if cfg.sp_axis is not None and _axis_size(cfg.sp_axis) > 1:
        loss = lax.pmean(loss, cfg.sp_axis)
    return loss
