"""Gradient wire compression (ops/compression.py) and the cast the
jit step's buckets take (parallel/train.py compression=...): registry
parsing (a typo or the retired `powersgd` raises by name, never trains
uncompressed), cast round-trip bounds, and the HLO identity pins:
compression="none" lowers BYTE-IDENTICAL to the plain builder, and
bf16 genuinely changes the program. The cast step against an
independent reference is tests/test_step_reference.py's."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.ops import compression as C


# ---------------------------------------------------------------------------
# Registry parsing
# ---------------------------------------------------------------------------

class TestRegistry:
    @pytest.mark.parametrize("raw,kind", [
        ("none", "none"), ("fp16", "fp16"), ("bf16", "bf16"),
    ])
    def test_accepted_spellings(self, raw, kind):
        assert C.resolve_compression(raw) == kind

    @pytest.mark.parametrize("raw", [
        "powersdg", "powersgd", "powersgd:2", "powersgd(rank=8)",
        "POWERSGD:1",
    ])
    def test_typo_raises_not_silently_uncompressed(self, raw,
                                                   monkeypatch):
        """A typo, and every spelling of the retired lossy option, is
        rejected by name with the accepted values — as an argument and
        as the knob a job's environment may still set."""
        with pytest.raises(ValueError,
                           match="unknown.*none / fp16 / bf16"):
            C.resolve_compression(raw)
        monkeypatch.setenv("HOROVOD_COMPRESSION", raw)
        with pytest.raises(ValueError, match=raw.lower()[:8]):
            C.resolve_compression()

    def test_knob_defaults_match_docs(self, monkeypatch):
        """The registry default the user guide's knob table states."""
        monkeypatch.delenv("HOROVOD_COMPRESSION", raising=False)
        assert C.resolve_compression() == "none"

    def test_tag_of_every_eager_value(self):
        assert C.tag_of(C.Compression.none) == "none"
        assert C.tag_of(C.Compression.fp16) == "fp16"
        assert C.tag_of(C.Compression.bf16) == "bf16"
        assert not hasattr(C.Compression, "powersgd")


# ---------------------------------------------------------------------------
# Cast compressors: round-trip bounds
# ---------------------------------------------------------------------------

class TestCastRoundTrip:
    @pytest.mark.parametrize("comp,wire,rtol", [
        (C.Compression.fp16, jnp.float16, 1e-3),
        (C.Compression.bf16, jnp.bfloat16, 8e-3),
    ])
    def test_round_trip_relative_error(self, comp, wire, rtol):
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(256,)), jnp.float32)
        c, ctx = comp.compress(x)
        assert c.dtype == wire and ctx == jnp.float32
        back = comp.decompress(c, ctx)
        assert back.dtype == jnp.float32
        assert float(jnp.max(jnp.abs(back - x)
                             / (jnp.abs(x) + 1e-12))) < rtol

    def test_integer_leaves_pass_through(self):
        x = jnp.arange(8, dtype=jnp.int32)
        c, ctx = C.Compression.fp16.compress(x)
        assert c.dtype == jnp.int32 and ctx is None
        assert (C.Compression.fp16.decompress(c, ctx) == x).all()

    def test_bf16_survives_fp16_overflow_range(self):
        """The TPU-native wire choice: 1e5 overflows fp16 to inf but
        bf16 keeps the exponent (the no-overflow-cliff rationale)."""
        x = jnp.asarray([1e5], jnp.float32)
        cf, _ = C.Compression.fp16.compress(x)
        cb, _ = C.Compression.bf16.compress(x)
        assert bool(jnp.isinf(cf.astype(jnp.float32))[0])
        assert float(cb.astype(jnp.float32)[0]) == pytest.approx(
            1e5, rel=0.01)


# ---------------------------------------------------------------------------
# The jit plane: build_train_step(compression=...)
# ---------------------------------------------------------------------------

def _mesh():
    return Mesh(np.array(jax.devices()[:8]), axis_names=("data",))


def _loss(params, batch):
    h = jnp.tanh(batch[:, None] * params["w1"][None, :])
    return jnp.mean((h @ params["w2"]) ** 2) + jnp.mean(params["b"] ** 2)


def _params():
    return {"w1": jnp.arange(32.0) / 32.0,
            "w2": jnp.ones((32, 16)) * 0.1 + jnp.arange(
                32.0 * 16).reshape(32, 16) * 1e-3,
            "b": jnp.zeros(3)}


def _batch(mesh):
    return jax.device_put(jnp.arange(8.0),
                          NamedSharding(mesh, P("data")))


class TestJitPlane:
    def test_none_is_byte_identical_hlo(self, monkeypatch):
        """compression="none" (explicit AND knob-default) lowers the
        IDENTICAL program to a build that never heard of compression
        — the transform is free when off. bf16 must genuinely change
        the program, or the knob is theater."""
        from horovod_tpu.parallel.train import build_train_step
        for k in ("HOROVOD_COMPRESSION", "HOROVOD_NUMERICS_GUARD"):
            monkeypatch.delenv(k, raising=False)
        mesh = _mesh()
        opt = optax.sgd(0.1)
        params = _params()
        st = opt.init(params)
        batch = _batch(mesh)
        base = build_train_step(_loss, opt, mesh, donate=False,
                                overlap_threshold=512)
        expl = build_train_step(_loss, opt, mesh, donate=False,
                                overlap_threshold=512,
                                compression="none")
        hlo_base = base.lower(params, st, batch).as_text()
        assert expl.lower(params, st, batch).as_text() == hlo_base
        monkeypatch.setenv("HOROVOD_COMPRESSION", "none")
        knob = build_train_step(_loss, opt, mesh, donate=False,
                                overlap_threshold=512)
        assert knob.lower(params, st, batch).as_text() == hlo_base
        monkeypatch.setenv("HOROVOD_COMPRESSION", "bf16")
        cast = build_train_step(_loss, opt, mesh, donate=False,
                                overlap_threshold=512)
        assert cast.lower(params, st, batch).as_text() != hlo_base

    def test_retired_knob_value_stops_the_build(self, monkeypatch):
        """A job whose environment still asks for powersgd does not
        get a step that trains on without it."""
        from horovod_tpu.parallel.train import build_train_step
        monkeypatch.setenv("HOROVOD_COMPRESSION", "powersgd:4")
        with pytest.raises(ValueError, match="none / fp16 / bf16"):
            build_train_step(_loss, optax.sgd(0.1), _mesh())
