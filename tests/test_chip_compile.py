"""Compile the main path's kernels and step programs for a described
TPU v5e 2x2 with the installed TPU compiler — no chip attached, nothing
runs. The only test file that describes the chip: the topology call
loads the TPU library, which one process at a time may hold, so it
happens inside a module-scoped fixture (never at import, in a skipif or
in parametrize arguments) and every compile stays in this process.
"""

import os
import re
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

HBM_BYTES = 16e9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent
    # cache but cannot be read back without one: keep it off here.
    # The chip runs with x64 off (conftest turns it on for the CPU
    # tests); Mosaic refuses the i64 block indices x64 would make.
    was = {k: getattr(jax.config, k) for k in
           ("jax_enable_compilation_cache", "jax_enable_x64")}
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()
    yield t
    for k, v in was.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _on(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=sharding), tree)


@pytest.mark.parametrize("n", chip_smoke.ADASUM_SIZES,
                         ids=["64MiB", "odd"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_adasum_pair_combine_compiles_to_custom_call(topo, n, dtype):
    from horovod_tpu.ops.pallas_kernels import adasum_pair_combine
    x = jax.ShapeDtypeStruct((n,), dtype,
                             sharding=SingleDeviceSharding(topo.devices[0]))
    compiled = adasum_pair_combine.lower(x, x, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape", [(2, 2048, 32, 8, 128),
                                   (1, 256, 32, 8, 128),
                                   (2, 2048, 32, 32, 128),
                                   (1, 2048, 8, 2, 256),
                                   (1, 640, 16, 2, 128),
                                   (1, 32768, 6, 1, 128),
                                   (1, 65536, 6, 1, 128),
                                   (1, 16384, 10, 1, 128)],
                         ids=["mistral-window", "mistral-sample", "mha",
                              "head256", "group8-seq640",
                              "longest-one-kernel", "over-budget-split",
                              "jamba-group10"])
def test_flash_attention_forward_and_backward_compile(topo, shape):
    """The fused kernels at the Mistral cells' shapes and the blocks
    the rule gives them (512 at seq 2048, 256 at the seq-256 sample):
    one custom call forward, two with backward (the one backward
    kernel), and no (B, H, L, L) tensor left in the program. At the
    longest sequence whose resident dK / dV fit `RESIDENT_KV_CAP` the
    one kernel still fits the v5e's VMEM; over it the backward is the
    two kernels dQ and dK/dV, three calls, and they fit too."""
    from horovod_tpu.parallel import fused_attention
    from horovod_tpu.parallel.ring_attention import flash_attention_path
    B, L, H, Hkv, D = shape
    assert fused_attention.block_size(L) == {2048: 512, 256: 256,
                                             640: 128}.get(L, 512)
    one = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((B, L, H, D), jnp.bfloat16, sharding=one)
    k = jax.ShapeDtypeStruct((B, L, Hkv, D), jnp.bfloat16, sharding=one)

    def fwd(q, k, v):
        return flash_attention_path(q, k, v, True, D ** -0.5)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    one = fused_attention.one_kernel_backward(q.shape, k.shape, k.shape)
    assert one is (L <= 32768)
    for fn, calls in ((fwd, 1), (bwd, 2 if one else 3)):
        hlo = jax.jit(fn).lower(q, k, k).compile().as_text()
        assert hlo.count('custom_call_target="tpu_custom_call"') == calls
        assert f"[{B},{H},{L},{L}]" not in hlo
    names = {re.search(r"hvd_fused_attention_[a-z]+", line).group()
             for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line}
    assert names == ({"hvd_fused_attention_fwd", "hvd_fused_attention_bwd"}
                     if one else {"hvd_fused_attention_fwd",
                                  "hvd_fused_attention_dq",
                                  "hvd_fused_attention_dkv"})


@pytest.mark.parametrize("L, window, steps", [
    (16384, 4096, 9), (8192, 4096, 9), (16384, 4000, 9), (2048, 640, 3)],
    ids=["trinity-window", "trinity-sample", "edge-inside-a-block",
         "short"])
def test_windowed_attention_forward_and_backward_compile(topo, L, window,
                                                         steps):
    """The fused kernels with a sliding window at the Trinity cell's
    share (12 q heads in groups of 6 on 2 kv heads of 128, blocks of
    512): three heads a grid step, a walk of `steps` key blocks a
    query block where the causal walk has L / 512, one custom call
    forward and two with backward."""
    from horovod_tpu.parallel import fused_attention
    from horovod_tpu.parallel.ring_attention import flash_attention_path
    assert fused_attention.step_heads(12, 2, 128, 128) == (3, 1, 2)
    assert fused_attention.walk_steps(L, 512, window) == steps < L // 512
    one = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((1, L, 12, 128), jnp.bfloat16, sharding=one)
    k = jax.ShapeDtypeStruct((1, L, 2, 128), jnp.bfloat16, sharding=one)

    def fwd(q, k, v):
        return flash_attention_path(q, k, v, True, 128 ** -0.5, window)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    for fn, calls in ((fwd, 1), (bwd, 2)):
        hlo = jax.jit(fn).lower(q, k, k).compile().as_text()
        assert hlo.count('custom_call_target="tpu_custom_call"') == calls
        assert f"[1,12,{L},{L}]" not in hlo


@pytest.mark.parametrize("L", [32768, 16384], ids=["cell", "half"])
def test_sparse_attention_kernels_compile_at_the_cell_s_shapes(topo, L):
    """The block-sparse layer beyond its dense length, as a TPU traces
    it at the MiniCPM-SALA cell's share (16 q heads on one kv head of
    128, 64 blocks of 64 keys a query): the selection in XLA, then the
    kernels that walk the table of visited blocks, four heads a grid
    step at blocks of 512, the backward one kernel with the kv head's
    f32 dK / dV resident (32 MiB at the cell's length, 16 at half of
    it, within the VMEM limit it declares); no (L x L) array and no
    whole pooled score tensor (16 heads x L x 2,047) left in the
    program."""
    import horovod_tpu as hvd
    from horovod_tpu.parallel import sparse_attention as sa
    spec = sa.SparseSpec()
    one = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((1, L, 16, 128), jnp.bfloat16, sharding=one)
    k = jax.ShapeDtypeStruct((1, L, 1, 128), jnp.bfloat16, sharding=one)
    assert sa.kernel_block(L, spec) == 512

    def fwd(q, k, v):
        return sa.sparse_attention(q, k, v, spec)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    def traces():
        return hvd.metrics().get("hvd_attention_traces_total", {}).get(
            ("sparse_blocks",), 0)
    for fn, calls in ((fwd, ("fwd",)), (bwd, ("fwd", "bwd"))):
        before = traces()
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            lowered = jax.jit(fn).lower(q, k, k)
        assert traces() == before + 1
        hlo = lowered.compile().as_text()
        assert hlo.count('custom_call_target="tpu_custom_call"') \
            == len(calls)
        for name in calls:
            assert len(re.findall(
                rf"%hvd_sparse_attention_{name}[.\d]* = ", hlo)) == 1, name
        assert "hvd_fused_attention" not in hlo
        assert f",{L},{L}]" not in hlo and f"[{L},{L}" not in hlo
        assert f"{L},{L // 16 - 1}]" not in hlo


def test_sparse_layer_at_its_dense_length_runs_the_fused_kernels(topo):
    """At or under `dense_len` the layer is `attention()`: on a TPU
    the causal fused kernels, as they are."""
    from horovod_tpu.parallel import sparse_attention as sa
    one = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((1, 8192, 16, 128), jnp.bfloat16, sharding=one)
    k = jax.ShapeDtypeStruct((1, 8192, 1, 128), jnp.bfloat16, sharding=one)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = jax.jit(lambda q, k, v: sa.sparse_attention(
            q, k, v, sa.SparseSpec())).lower(q, k, k)
    hlo = lowered.compile().as_text()
    assert len(re.findall(r"%hvd_fused_attention_fwd[.\d]* = ", hlo)) == 1
    assert "hvd_sparse_attention" not in hlo


@pytest.mark.parametrize("kernels", [True, False],
                         ids=["kernels", "chunks"])
def test_linear_attention_compiles_at_the_cell_s_shapes(topo, kernels):
    """The lightning core forward and backward at 16 heads of 128 and
    32,768 positions, chunks of 256, no (L x L) array. As a TPU traces
    it in bf16: three kernels (forward, dQ, dK/dV) with the state in
    VMEM and no state in HBM. The `jax.numpy` path (any other backend
    or dtype): the states of 128 chunks (134 MB a pass), under 1.5 GB
    of temporaries."""
    import horovod_tpu as hvd
    from horovod_tpu.parallel import linear_attention as la
    from horovod_tpu.tracing import device_scope
    one = SingleDeviceSharding(topo.devices[0])
    L = 32768
    x = jax.ShapeDtypeStruct((1, L, 16, 128), jnp.bfloat16, sharding=one)
    slopes = jax.ShapeDtypeStruct((16,), jnp.float32, sharding=one)

    def core(*a):
        # XLA names a custom call after the innermost name of its
        # scope: under the model's scope the kernel's own
        with device_scope("hvd.attn.linear"):
            return la.linear_attention(*a)

    def both(q, k, v, slopes):
        return jax.value_and_grad(
            lambda *a: core(*a, slopes).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)
    label = ("kernel" if kernels else "chunks",)
    before = hvd.metrics().get("hvd_linear_attention_traces_total",
                               {}).get(label, 0)
    with mock.patch.object(jax, "default_backend",
                           lambda: "tpu" if kernels else "cpu"):
        lowered = jax.jit(both).lower(x, x, x, slopes)
    assert hvd.metrics()["hvd_linear_attention_traces_total"][label] \
        == before + 1
    compiled = lowered.compile()
    hlo = compiled.as_text()
    assert f",{L},{L}]" not in hlo and f"[{L},{L}" not in hlo
    # a state a chunk and head, whatever order XLA lays them in
    states = re.search(r"f32\[(1,)?(128,16|16,128|2048),128,128\]", hlo)
    if kernels:
        for name in ("fwd", "dq", "dkv"):
            assert len(re.findall(
                rf"%hvd_linear_attention_{name}[.\d]* = ", hlo)) == 1, name
        assert not states
    else:
        assert "tpu_custom_call" not in hlo and states
        assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


@pytest.mark.parametrize("kernels", [True, False],
                         ids=["kernels", "chunks"])
def test_selective_scan_compiles_at_the_cell_s_shapes(topo, kernels):
    """The Mamba mixers' scan forward and backward at 2,560 channels of
    16 states and 16,384 positions, chunks of 32. As a TPU traces it in
    bf16: two kernels (forward, backward) with the state in VMEM and no
    (L x C x 16) array in HBM. The `jax.numpy` path (any other backend
    or dtype): a chunk's (32 x C x 16) arrays at a time, under 1.5 GB
    of temporaries."""
    import horovod_tpu as hvd
    from horovod_tpu.parallel import selective_scan as ss
    from horovod_tpu.tracing import device_scope
    one = SingleDeviceSharding(topo.devices[0])
    L, C, N = 16384, 2560, 16

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)
    x = shape(1, L, C, dtype=jnp.bfloat16)
    args = (x, shape(1, L, C), shape(C, N), shape(1, L, N), shape(1, L, N),
            shape(C), x)

    def both(*a):
        def scan(*a):
            with device_scope("hvd.ssm.scan"):
                return ss.selective_scan(*a)
        return jax.value_and_grad(
            lambda *a: scan(*a).astype(jnp.float32).sum(),
            argnums=tuple(range(7)))(*a)
    label = ("kernel" if kernels else "chunks",)
    before = hvd.metrics().get("hvd_selective_scan_traces_total",
                               {}).get(label, 0)
    with mock.patch.object(jax, "default_backend",
                           lambda: "tpu" if kernels else "cpu"):
        lowered = jax.jit(both).lower(*args)
    assert hvd.metrics()["hvd_selective_scan_traces_total"][label] \
        == before + 1
    compiled = lowered.compile()
    hlo = compiled.as_text()
    # a state a position and channel, whatever order XLA lays it in
    assert not re.search(rf"\[(1,)?({L},{C},{N}|{L},{N},{C}|{C},{L},{N})\]",
                         hlo)
    if kernels:
        for name in ("fwd", "bwd"):
            assert len(re.findall(
                rf"%hvd_selective_scan_{name}[.\d]* = ", hlo)) == 1, name
    else:
        assert "tpu_custom_call" not in hlo
        assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


@pytest.mark.parametrize("B, L", [(2, 4096), (1, 256)],
                         ids=["window", "sample"])
def test_latent_attention_core_compiles_with_v_at_its_own_width(topo, B, L):
    """q / k 192 wide, v 128 (the latent-attention cell): every
    operand of the fused kernels at its own width (q, k, dQ, dK 32 x
    192 columns; v, the output, dO and dV 32 x 128), nothing padded to
    256 anywhere in the program, no (B, H, L, L) tensor."""
    from horovod_tpu.parallel.ring_attention import flash_attention_path
    one = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((B, L, 32, 192), jnp.bfloat16, sharding=one)
    v = jax.ShapeDtypeStruct((B, L, 32, 128), jnp.bfloat16, sharding=one)

    def bwd(q, k, v):
        return jax.grad(lambda *a: flash_attention_path(
            *a, True, 0.1447).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)
    hlo = jax.jit(bwd).lower(q, q, v).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    assert f"[{B},32,{L},{L}]" not in hlo
    dq, dk, dv = jax.eval_shape(bwd, q, q, v)
    assert (dq.shape, dk.shape, dv.shape) == (q.shape, q.shape, v.shape)
    assert f"[{B},{L},32,256]" not in hlo and f"[{B},{L},8192]" not in hlo
    # What each kernel gives back and is handed, in operand order: the
    # backward's dQ, dK, dV, then q, k, v, dO.
    wide, narrow = f"bf16[{B},{L},6144]", f"bf16[{B},{L},4096]"
    calls = {}
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = next(n for n in ("_fwd", "_bwd")
                        if f"hvd_fused_attention{n}" in line)
            calls[name] = re.findall(r"bf16\[[0-9,]+\]", line)
    assert calls["_fwd"] == [narrow, wide, wide, narrow]
    assert calls["_bwd"] == [wide, wide, narrow, wide, wide, narrow,
                             narrow]


@pytest.mark.parametrize("k, n, m, tile", [
    (3584, 1024, 34816, 256), (1024, 3584, 34816, 256),
    (3072, 3072, 73728, 1024), (896, 2304, 135168, 256)],
    ids=["gate-up", "down", "trinity", "mellum-down"])
def test_grouped_matmul_kernels_compile(topo, k, n, m, tile):
    """The expert layer's grouped matmuls at the published experts'
    widths over their cells' dispatch buffers (`xing4`: 34,816 rows,
    8,192 tokens x 4 choices and a tile of 256 an expert; `trinity`:
    73,728 rows, 16,384 x 4 and a tile of 1,024 an expert; `mellum`'s
    down projection: 135,168 rows, 16,384 x 8 and a tile of 256 an
    expert, dw in 128-lane column blocks): forward, dx and dw are one
    Mosaic kernel each."""
    from horovod_tpu.parallel import grouped_matmul as gm
    one = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one)
    w = jax.ShapeDtypeStruct((8, k, n), jnp.bfloat16, sharding=one)
    rows = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one)

    def both(x, w, rows):
        def loss(x, w):
            out = gm.grouped_matmul_kernels(x, w, rows, tile_m=tile)
            return jnp.sum(jnp.square(out.astype(jnp.float32)))
        return jax.value_and_grad(loss, (0, 1))(x, w)
    hlo = jax.jit(both).lower(x, w, rows).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 3
    for name in ("hvd_grouped_matmul_fwd", "hvd_grouped_matmul_dx",
                 "hvd_grouped_matmul_dw"):
        assert name in hlo


@pytest.mark.parametrize("T, D, n, tile, k", [
    (8192, 3584, 34816, 256, 4), (16384, 3072, 73728, 1024, 4),
    (8192, 3072, 40960, 1024, 4), (16384, 2304, 135168, 256, 8)],
    ids=["xing4", "trinity", "trinity-sample", "mellum"])
def test_row_movers_compile(topo, T, D, n, tile, k):
    """The expert layer's row movers and their pack at the cells' shapes
    (T tokens of k choices, a buffer of n rows in tiles of `tile`;
    `mellum`'s rows are 4.5 chunks of packed words): each is one
    Mosaic kernel under its own name, within the VMEM the call allows
    itself (a compile that passes: the fetch scratch, the f32 stage
    and the double-buffered blocks)."""
    from horovod_tpu.parallel import row_movers as rm
    one = SingleDeviceSharding(topo.devices[0])

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    bf16, f32, i32, u32 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.uint32
    assert rm.supported(T, k, D)
    movers = {
        "pack": (rm.pack_rows, (on((T, D), f32),), "hvd_moe_rows_pack"),
        "dispatch": (lambda tok, index, live: rm.rows_in(
            tok, index, live, width=D, tile_m=tile),
            (on((T, 8, 256), u32), on((n,), i32), on((1,), i32)),
            "hvd_moe_rows_in"),
        "combine-backward": (lambda d_out, index, live, gate: rm.rows_in(
            d_out, index, live, width=D, tile_m=tile, scale=gate),
            (on((T, 8, 256), u32), on((n,), i32), on((1,), i32),
             on((n,), f32)), "hvd_moe_rows_in"),
        "combine-backward-gates": (rm.rows_dot,
                                   (on((n, D), bf16), on((T, k), i32),
                                    on((T, D), f32)), "hvd_moe_rows_dot"),
        "combine": (lambda ys, code, gates: rm.rows_out(
            ys, code, gates, out_dtype=f32),
            (on((n, D), bf16), on((T, k), i32), on((T, k), f32)),
            "hvd_moe_rows_out"),
        "dispatch-backward": (lambda d_xs, code: rm.rows_out(d_xs, code),
                              (on((n, D), bf16), on((T, k), i32)),
                              "hvd_moe_rows_out"),
    }
    for fn, args, name in movers.values():
        hlo = jax.jit(fn).lower(*args).compile().as_text()
        assert hlo.count('custom_call_target="tpu_custom_call"') == 1
        assert name in hlo
        assert f"[{n},{D}]" not in hlo.replace(
            f"bf16[{n},{D}]", "")          # the buffer only in bf16
        assert not re.search(rf"\[\d+,{D}\][^=]* gather\(", hlo)


@pytest.mark.parametrize("m, k, n, tile", [
    (34816, 3584, 1024, 256), (73728, 3072, 3072, 1024),
    (135168, 2304, 896, 256)], ids=["xing4", "trinity", "mellum"])
def test_grouped_swiglu_kernels_compile(topo, m, k, n, tile):
    """Gate and up with the SwiGLU inside at the cells' shapes:
    forward (both products and the activation), the two cotangents,
    dx as one sum and dw twice are five Mosaic kernels, each within
    the VMEM the call allows itself, and the cotangents take the
    products' buffers."""
    from horovod_tpu.parallel import grouped_matmul as gm
    one = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one)
    w = jax.ShapeDtypeStruct((8, k, n), jnp.bfloat16, sharding=one)
    rows = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one)

    def both(x, w_gate, w_up, rows, d_act):
        act, pullback = jax.vjp(
            lambda *a: gm.grouped_swiglu_kernels(*a, rows, tile_m=tile),
            x, w_gate, w_up)
        return act, pullback(d_act)
    hlo = jax.jit(both).lower(
        x, w, w, rows, jax.ShapeDtypeStruct((m, n), jnp.bfloat16,
                                            sharding=one)).compile().as_text()
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 5
    # outside a scope XLA names the call after the pass as well
    for name, calls in (("hvd_grouped_matmul_swiglu_fwd", 1),
                        ("hvd_grouped_matmul_swiglu_dh", 1),
                        ("hvd_grouped_matmul_swiglu_dx", 1),
                        ("hvd_grouped_matmul_dw", 2)):
        found = [line for line in kernels
                 if re.match(rf"\s*%\w*{name}_*[.\d]* = ", line)]
        assert len(found) == calls, name
    assert "output_to_operand_aliasing={{0}: (3, {}), {1}: (4, {})}" in \
        next(line for line in kernels if "swiglu_dh" in line)


@pytest.mark.parametrize("T, D, F, tile, n_rows, held, k", [
    (8192, 3584, 1024, None, 34816, 8, 4),
    (16384, 3072, 3072, 1024, 73728, 8, 4),
    (16384, 2304, 896, None, 135168, 16, 8)],
    ids=["xing4", "trinity", "mellum"])
def test_expert_layer_on_the_tpu_takes_the_live_tiles(topo, T, D, F, tile,
                                                      n_rows, held, k):
    """What a TPU trace of `expert_share_ffn` takes at the cells'
    shapes (`mellum`: hidden 2304, 4.5 chunks of packed words, and 8
    choices a token): the counter says `sorted_live_tiles`, and forward
    and backward are fifteen Mosaic kernels (the pack, rows in and rows
    out twice each, the gates' products once; gate and up with the
    SwiGLU inside: forward, cotangents, dx; the down projection's
    forward and dx; dw three times) with no gather of a row of the
    model's width left in the program, and nothing elementwise on an
    operand of the dispatch buffer's shape: neither the SwiGLU, its
    backward nor a sum of two dx is XLA's, whose loop fusions would
    walk the dead tiles too."""
    import horovod_tpu as hvd
    from horovod_tpu.parallel import moe
    one = SingleDeviceSharding(topo.devices[0])

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    bf16 = jnp.bfloat16

    def both(tokens, experts, gates, w_gate, w_up, w_down):
        def loss(tokens, gates, w_gate, w_up, w_down):
            return jnp.sum(moe.expert_share_ffn(
                tokens, experts, gates, w_gate, w_up, w_down, 0, tile))
        return jax.value_and_grad(loss, (0, 1, 2, 3, 4))(
            tokens, gates, w_gate, w_up, w_down)
    label = ("sorted_live_tiles",)
    before = hvd.metrics().get("hvd_moe_traces_total", {}).get(label, 0)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = jax.jit(both).lower(
            on((T, D), bf16), on((T, k), jnp.int32),
            on((T, k), jnp.float32), on((held, D, F), bf16),
            on((held, D, F), bf16), on((held, F, D), bf16))
    assert hvd.metrics()["hvd_moe_traces_total"][label] == before + 1
    buffer = rf"tensor<{n_rows}x({D}|{F})x"
    text = lowered.as_text()
    assert re.search(buffer, text)
    assert not [line for line in text.splitlines()
                if re.search(buffer, line) and re.search(
                    r"stablehlo\.(logistic|multiply|add|convert)\b", line)]
    hlo = lowered.compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 15
    for name, calls in (("hvd_moe_rows_pack", 2), ("hvd_moe_rows_in", 2),
                        ("hvd_moe_rows_out", 2),
                        ("hvd_moe_rows_dot", 1),
                        ("hvd_grouped_matmul_swiglu_fwd", 1),
                        ("hvd_grouped_matmul_swiglu_dh", 1),
                        ("hvd_grouped_matmul_swiglu_dx", 1),
                        ("hvd_grouped_matmul_fwd", 1),
                        ("hvd_grouped_matmul_dx", 1),
                        ("hvd_grouped_matmul_dw", 3)):
        assert len(re.findall(rf"%{name}[.\d]* = ", hlo)) == calls, name
    assert not re.search(rf"\[\d+,{D}\][^=]* gather\(", hlo)
    # the buffer's shape only on the kernels and on what names their
    # results: no fusion, copy or add of XLA's over it
    for line in hlo.splitlines():
        if re.search(rf"= [a-z0-9]+\[{n_rows},({D}|{F})\]", line):
            assert re.search(r" (custom-call|get-tuple-element|parameter)\(",
                             line), line


def _flagship_lowered(devices, global_batch):
    from horovod_tpu.models import transformer as tfm
    mesh = Mesh(np.array(devices), axis_names=("data",))
    shape = chip_smoke.FLAGSHIP
    cfg, opt, step = chip_smoke.flagship_step(shape, mesh)
    params = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    opt_state = jax.eval_shape(opt.init, params)
    rep = NamedSharding(mesh, P())
    tok = jax.ShapeDtypeStruct((global_batch, shape["seq"]), jnp.int32,
                               sharding=NamedSharding(mesh, P("data")))
    return step.lower(_on(params, rep), _on(opt_state, rep),
                      {"tokens": tok, "targets": tok})


def _flagship_compiled(devices, global_batch):
    return _flagship_lowered(devices, global_batch).compile()


def _without_options():
    """`build_train_step` as it was before it chose compile options."""
    from horovod_tpu.parallel import train
    return mock.patch.object(train, "async_reduce_hbm_bytes",
                             lambda mesh: None)


def test_flagship_step_fits_one_chip(topo):
    compiled = _flagship_compiled(topo.devices[:1],
                                  chip_smoke.FLAGSHIP["batch"])
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def test_flagship_step_four_chip_data_mesh(topo):
    from horovod_tpu.parallel.train import last_overlap_info
    compiled = _flagship_compiled(topo.devices,
                                  4 * chip_smoke.FLAGSHIP["batch"])
    assert last_overlap_info()["buckets"] > 0
    hlo = compiled.as_text()
    assert " all-reduce(" in hlo or " all-reduce-start(" in hlo
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def test_flagship_four_chip_reduction_is_asynchronous(topo):
    """Under the options `build_train_step` picks for a TPU mesh with
    a live axis, the compiler makes start / done pairs of gradient
    all-reduces (it leaves every one synchronous without them), and
    the step needs no more memory for it than 1 % of what the
    optionless step needs: the scheduler may not pay for overlap with
    live ranges across the program's peak."""
    from horovod_tpu.parallel.train import (ASYNC_REDUCE_OPTIONS,
                                            MEMORY_LIMIT_OPTION,
                                            last_overlap_info)
    batch = 4 * chip_smoke.FLAGSHIP["batch"]
    lowered = _flagship_lowered(topo.devices, batch)
    assert last_overlap_info()["compiler_options"] == sorted(
        ASYNC_REDUCE_OPTIONS) + [MEMORY_LIMIT_OPTION]
    assert dict(lowered._lowering._compiler_options_kvs) == {
        **ASYNC_REDUCE_OPTIONS, MEMORY_LIMIT_OPTION: 22}
    with _without_options():
        plain = _flagship_lowered(topo.devices, batch)
    assert last_overlap_info()["compiler_options"] == []
    assert plain.as_text() == lowered.as_text()   # options, not text
    shipped, plain = lowered.compile(), plain.compile()
    pairs = re.compile(r"async-collective-start|all-reduce-start\(")
    assert pairs.search(shipped.as_text())
    assert not pairs.search(plain.as_text())
    mem, base = shipped.memory_analysis(), plain.memory_analysis()
    assert mem.temp_size_in_bytes - base.temp_size_in_bytes <= 0.01 * (
        base.argument_size_in_bytes + base.temp_size_in_bytes)


def test_one_chip_step_gets_no_compile_option(topo):
    """No live axis, no option: the one-chip step lowers to the text,
    and compiles under the (empty) options, it had before the rule."""
    from horovod_tpu.parallel.train import (async_reduce_hbm_bytes,
                                            last_overlap_info)
    one = topo.devices[:1]
    assert async_reduce_hbm_bytes(
        Mesh(np.array(one), axis_names=("data",))) is None
    lowered = _flagship_lowered(one, chip_smoke.FLAGSHIP["batch"])
    assert last_overlap_info()["compiler_options"] == []
    with _without_options():
        plain = _flagship_lowered(one, chip_smoke.FLAGSHIP["batch"])
    assert lowered.as_text() == plain.as_text()
    assert not lowered._lowering._compiler_options_kvs


def test_resnet50_step_fits_one_chip(topo):
    from horovod_tpu.models.resnet import init_resnet
    mesh = Mesh(np.array(topo.devices[:1]), axis_names=("data",))
    shape = chip_smoke.RESNET
    model, opt, step = chip_smoke.resnet_step(shape, mesh)
    variables = jax.eval_shape(
        lambda: init_resnet(model, jax.random.PRNGKey(0),
                            shape["image"]))
    params, stats = variables["params"], variables["batch_stats"]
    opt_state = jax.eval_shape(opt.init, params)
    rep, data = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    b, px = shape["batch"], shape["image"]
    batch = {
        "images": jax.ShapeDtypeStruct((b, px, px, 3), jnp.float32,
                                       sharding=data),
        "labels": jax.ShapeDtypeStruct((b,), jnp.int32, sharding=data),
        "batch_stats": _on(stats, rep)}
    compiled = step.lower(_on(params, rep), _on(opt_state, rep),
                          batch).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
