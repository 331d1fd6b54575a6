"""Gradient compression for collectives.

Mirrors the reference's Compression API
(reference: horovod/torch/compression.py / horovod/tensorflow/compression.py
— Compression.none / Compression.fp16, Compressor.compress/decompress).

On TPU the natural wire dtype is bfloat16 (same byte savings as fp16,
no overflow cliff, native MXU dtype), so `Compression.bf16` is added and
`Compression.fp16` is kept for parity.

`resolve_compression` parses the HOROVOD_COMPRESSION knob (`none` /
`fp16` / `bf16`) for the jit plane's per-bucket wire cast
(parallel/train.py); the numerics finite-flag vote never rides a cast
carrier (HVD007 check (e)).
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp


class Compressor:
    @staticmethod
    def compress(tensor):
        """Returns (compressed_tensor, context_for_decompress)."""
        raise NotImplementedError

    @staticmethod
    def decompress(tensor, ctx):
        raise NotImplementedError


class NoneCompressor(Compressor):
    @staticmethod
    def compress(tensor):
        return tensor, None

    @staticmethod
    def decompress(tensor, ctx):
        return tensor


class _CastCompressor(Compressor):
    wire_dtype = None

    @classmethod
    def compress(cls, tensor):
        tensor = jnp.asarray(tensor)
        if jnp.issubdtype(tensor.dtype, jnp.floating):
            return tensor.astype(cls.wire_dtype), tensor.dtype
        return tensor, None

    @classmethod
    def decompress(cls, tensor, ctx):
        return tensor.astype(ctx) if ctx is not None else tensor


class FP16Compressor(_CastCompressor):
    wire_dtype = jnp.float16


class BF16Compressor(_CastCompressor):
    wire_dtype = jnp.bfloat16


def wire_dtype_of(compression, dtype) -> jnp.dtype:
    """The on-wire dtype a compressor produces for inputs of `dtype`,
    WITHOUT materializing a cast. Used by the negotiation layer to
    build fuse keys (same wire dtype == fusable) and by the dispatch
    kernels, which run compress/decompress INSIDE the fused XLA
    program — one launch per agreed batch instead of per-tensor cast
    launches (the analog of the reference doing scale/cast as part of
    MemcpyInFusionBuffer, horovod/common/ops/gpu_operations.cc batched
    scale kernels)."""
    dt = jnp.dtype(dtype)
    wd = getattr(compression, "wire_dtype", None)
    if wd is not None and jnp.issubdtype(dt, jnp.floating):
        return jnp.dtype(wd)
    return dt


def tag_of(compression) -> str:
    """Canonical metric/digest tag of an eager-API compressor value
    ("none" / "fp16" / "bf16") — the label
    `hvd_wire_bytes_total{compression=...}` carries."""
    if compression is NoneCompressor:
        return "none"
    if compression is FP16Compressor:
        return "fp16"
    if compression is BF16Compressor:
        return "bf16"
    name = getattr(compression, "__name__",
                   type(compression).__name__)
    return str(name).lower()


def compressor_for(raw_dtype, wire_dtype):
    """The Compressor class whose compress() maps `raw_dtype` to
    `wire_dtype`. Used by joined ranks to reconstruct the live ranks'
    compressor from the negotiated signature so a zero-fill entry
    lowers the identical fused program (same compress cast) the live
    ranks do."""
    raw, wire = jnp.dtype(raw_dtype), jnp.dtype(wire_dtype)
    if wire == raw:
        return NoneCompressor
    if wire == jnp.float16:
        return FP16Compressor
    if wire == jnp.bfloat16:
        return BF16Compressor
    raise ValueError(
        f"no compressor maps {raw} to wire dtype {wire}")


class Compression:
    """Namespace matching hvd.Compression."""
    none = NoneCompressor
    fp16 = FP16Compressor
    bf16 = BF16Compressor


ACCEPTED = ("none", "fp16", "bf16")


def resolve_compression(name: Optional[str] = None) -> str:
    """The HOROVOD_COMPRESSION knob (or an explicit value) as one of
    `ACCEPTED`. Anything else raises, naming the accepted values: a
    typo'd knob, or a job whose environment still asks for the retired
    lossy `powersgd`, must not silently train uncompressed."""
    if name is None:
        from ..common.config import env_value
        name = env_value("HOROVOD_COMPRESSION")
    raw = str(name).strip().lower()
    if raw not in ACCEPTED:
        raise ValueError(
            f"unknown HOROVOD_COMPRESSION value {raw!r} (expected "
            f"{' / '.join(ACCEPTED)}; powersgd is no longer "
            f"supported)")
    return raw
