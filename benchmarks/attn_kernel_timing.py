"""Device time of the fused and block-sparse attention kernels alone,
on the chip.

    python3 benchmarks/attn_kernel_timing.py [--steps 10] [--block-cap N]
        [--heads-cap N] [B,L,H,Hkv,Dqk,Dv[,window] | sparse:B,L,H,Hkv,D,Dv
        ...]

For each shape: seeded bf16 q, k, v and dO, one jitted program that
runs forward and backward, `--steps` calls of it under the profiler,
and from the trace the mean device time a call of each kernel and of
everything else in the program (the `di` row sums, pads, cuts). The
forms: `kernels` is `fused_attention._forward` and `_backward` on the
shapes as given (where `supported` takes them), the backward as
`one_kernel_backward` decides; `kernels_two` the same with the backward
as the two kernels dQ and dK/dV, whatever the rule says; a shape whose q / k
and v widths differ also runs `path` (`flash_attention_path` as it
is), `path_padded_qk` (q and k zero-padded to whole lanes, v at its
own width) and `path_one_width` (the path before PR 32: q, k and v
zero-padded to one width, the output cut back). A `sparse:` shape runs
`sparse_attention._forward` and `_backward` under the default
`SparseSpec` on a selection that keeps every causal block, so that the
walk visits every causal kernel block as the seeded weights' selection
does: `sparse` with the backward as `one_kernel_backward` decides,
`sparse_two` as dQ and dK/dV whatever it says. One JSON line a
measurement, also appended to `chiprun_out/attn_kernel_timing.jsonl`.
Fails where JAX finds no TPU: a CPU time is no device time.

The default shapes are the `xing4-29b-ep8.jit-dp1` cell's core as the
model calls it (q / k 192, v 128), with q / k at 256, with all at 256
(what the kernels ran before PR 32), the Mistral cells' core, and the
`trinity-large-ep32tp4.jit-dp1` cell's window and full layers, and
the `minicpm-sala-tp2vp8.jit-dp1` cell's block-sparse core.
`--block-cap` and `--heads-cap` are for sweeps only: they override
`BLOCK_CAP` and `HEADS_CAP` in this process.
"""

from __future__ import annotations

import argparse
import functools
import importlib
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from horovod_tpu.parallel import fused_attention as fa  # noqa: E402
from horovod_tpu.parallel import sparse_attention as sa  # noqa: E402
# `horovod_tpu.parallel.ring_attention` the attribute is the function.
ra = importlib.import_module("horovod_tpu.parallel.ring_attention")
from perfbench.trace_reduce import (OPS_LINE, instruction,  # noqa: E402
                                    newest_xplane, read_events)

DEFAULT = ["2,4096,32,32,192,128", "2,4096,32,32,256,128",
           "2,4096,32,32,256,256", "2,2048,32,8,128,128",
           "1,16384,12,2,128,128,4096", "1,16384,12,2,128,128",
           "sparse:1,32768,16,1,128,128"]
KERNELS = ("hvd_fused_attention_fwd", "hvd_fused_attention_dq",
           "hvd_fused_attention_dkv", "hvd_fused_attention_bwd")
SPARSE_KERNELS = tuple(k.replace("fused", "sparse") for k in KERNELS)
OUT = os.path.join("chiprun_out", "attn_kernel_timing.jsonl")


def _inputs(B, L, H, Hkv, Dqk, Dv):
    ks = jax.random.split(jax.random.PRNGKey(32), 4)
    draw = functools.partial(jax.random.normal, dtype=jnp.bfloat16)
    return (draw(ks[0], (B, L, H, Dqk)), draw(ks[1], (B, L, Hkv, Dqk)),
            draw(ks[2], (B, L, Hkv, Dv)), draw(ks[3], (B, L, H, Dv)))


def _kernels(q, k, v, do, scale, window=None, one=None):
    o, lse = fa._forward(q, k, v, scale, False, window)
    if one is None:
        one = fa.one_kernel_backward(q.shape, k.shape, v.shape)
    return o, fa._backward(q, k, v, o, lse, do, scale, False, window, one)


def _kernels_two(q, k, v, do, scale, window=None):
    return _kernels(q, k, v, do, scale, window, one=False)


def _causal_tables(B, L, Hkv, one: bool):
    """The kernels' tables of a selection that keeps, for every query,
    every block at or before its own."""
    spec = sa.SparseSpec()
    n = L // spec.block
    own = jnp.arange(L)[:, None] // spec.block
    chosen = jnp.broadcast_to(jnp.arange(n)[None, :] <= own, (B, Hkv, L, n))
    return sa.block_tables(chosen, sa.kernel_block(L, spec), spec,
                           transposed=not one)


def _sparse(q, k, v, do, words, walk, walk_t, scale):
    spec = sa.SparseSpec()
    o, lse = sa._forward(q, k, v, words, walk, spec, scale, False)
    return o, sa._backward(q, k, v, words, walk, walk_t, o, lse, do, spec,
                           scale, False)


def _path(q, k, v, do, scale):
    out, vjp = jax.vjp(
        lambda q, k, v: ra.flash_attention_path(q, k, v, True, scale),
        q, k, v)
    return out, vjp(do)


def _padded(x, width):
    return jnp.pad(x, ((0, 0),) * 3 + ((0, width - x.shape[-1]),))


def _lanes(width):
    return -(-width // fa.LANES) * fa.LANES


def _path_padded_qk(q, k, v, do, scale):
    width = _lanes(q.shape[-1])
    out, vjp = jax.vjp(
        lambda q, k, v: fa.fused_causal_attention(
            _padded(q, width), _padded(k, width), v, scale), q, k, v)
    return out, vjp(do)


def _path_one_width(q, k, v, do, scale):
    width = _lanes(max(q.shape[-1], v.shape[-1]))

    def path(q, k, v):
        out = fa.fused_causal_attention(
            _padded(q, width), _padded(k, width), _padded(v, width), scale)
        return out[..., :v.shape[-1]]
    out, vjp = jax.vjp(path, q, k, v)
    return out, vjp(do)


def _device_ms(trace_dir: str, steps: int, kernels=KERNELS):
    """Mean device ms a call by kernel, and of every other
    instruction, on the first chip."""
    planes = read_events(newest_xplane(trace_dir))
    ops = planes[sorted(p for p in planes if p.startswith("/device:TPU:"))[0]]
    by = dict.fromkeys(kernels, 0.0)
    by["other"] = 0.0
    for text, start, end in ops[OPS_LINE]:
        name = instruction(text)[0]
        key = next((k for k in kernels if k in name), "other")
        by[key] += (end - start) / 1e9 / steps
    return by


def measure(form: str, fn, shape, steps: int, window=None, tables=(),
            kernels=KERNELS):
    args = (*_inputs(*shape), *tables)
    scale = float(shape[4]) ** -0.5
    kw = {} if window is None else {"window": window}
    step = jax.jit(functools.partial(fn, scale=scale, **kw))
    jax.block_until_ready(step(*args))
    jax.block_until_ready(step(*args))
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(steps):
                out = step(*args)
            jax.block_until_ready(out)
        by = _device_ms(d, steps, kernels)
    dev = jax.devices()[0]
    line = {"form": form, "shape": list(shape), "window": window,
            "block": fa.block_size(shape[1]),
            "step_heads": fa.step_heads(*shape[2:]),
            "steps": steps,
            "fwd_ms": by[kernels[0]], "dq_ms": by[kernels[1]],
            "dkv_ms": by[kernels[2]], "bwd_ms": by[kernels[3]],
            "other_ms": by["other"],
            "sum_ms": sum(by.values()),
            "device": {"platform": dev.platform, "kind": dev.device_kind}}
    print(json.dumps(line), flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(json.dumps(line) + "\n")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("shapes", nargs="*", default=DEFAULT)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--block-cap", type=int, default=fa.BLOCK_CAP)
    ap.add_argument("--heads-cap", type=int, default=fa.HEADS_CAP)
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("attn_kernel_timing: no TPU; a time from "
                         f"{jax.default_backend()} is no device time")
    if a.block_cap != fa.BLOCK_CAP:
        fa.block_size = functools.partial(fa.block_size, cap=a.block_cap)
    if a.heads_cap != fa.HEADS_CAP:
        fa.HEADS_CAP = a.heads_cap
        fa.heads_per_step = functools.partial(fa.heads_per_step,
                                              cap=a.heads_cap)
    for text in a.shapes:
        sparse = text.startswith("sparse:")
        B, L, H, Hkv, Dqk, Dv, *window = tuple(
            int(x) for x in text.removeprefix("sparse:").split(","))
        shape, window = (B, L, H, Hkv, Dqk, Dv), (window or [None])[0]
        if sparse:
            q, k, v = (B, L, H, Dqk), (B, L, Hkv, Dqk), (B, L, Hkv, Dv)
            if not sa.supported(q, k, v, sa.SparseSpec()):
                raise SystemExit(f"attn_kernel_timing: the sparse kernels "
                                 f"do not take {text}")
            for form, one in (("sparse", fa.one_kernel_backward(q, k, v)),
                              ("sparse_two", False)):
                measure(form, _sparse, shape, a.steps,
                        tables=_causal_tables(B, L, Hkv, one),
                        kernels=SPARSE_KERNELS)
            continue
        if fa.supported((B, L, H, Dqk), (B, L, Hkv, Dqk), (B, L, Hkv, Dv)):
            for form, fn in (("kernels", _kernels),
                             ("kernels_two", _kernels_two)):
                measure(form, fn, shape, a.steps, window)
        if Dqk != Dv and window is None:
            for form, fn in (("path", _path),
                             ("path_padded_qk", _path_padded_qk),
                             ("path_one_width", _path_one_width)):
                measure(form, fn, shape, a.steps)
    return 0


if __name__ == "__main__":
    sys.exit(main())
