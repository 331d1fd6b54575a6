"""Readers that several per-layer metrics share. A metric whose cells
report different end-to-end metrics is split by suffix
(`layer_metrics/mfu.tok.py`, `mfu.img.py`) because `moves` names one;
the twins read the same number, which is computed here. `ctx` is the
driver's context (README.md)."""

from __future__ import annotations

from perfbench import peaks


def device_busy_ms(ctx):
    """Device time of one step: the union of the intervals in which an
    instruction ran on the first chip, over the traced steps."""
    traced = ctx["traced"]
    if traced is None:
        return None
    return 1e3 * traced["busy_first_chip_s"] / traced["steps"]


def device_idle_pct(ctx):
    """Share of the traced window in which no instruction ran on the
    first chip."""
    traced = ctx["traced"]
    if traced is None:
        return None
    return 100.0 * (1 - traced["busy_first_chip_s"] / traced["window_s"])


def mfu(ctx):
    """Model FLOP/s utilisation: the operations the forward and
    backward passes require for one unit of work (the model adapter's
    `flops_per_unit`, recompute not counted) times the window's rate on
    one chip, over the chip's published bf16 peak."""
    peak = peaks.lookup(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * ctx["flops_per_unit"] * ctx["rate_per_chip"] / peak


def per_traced_step_ms(ctx, key):
    """A collective time of the reduction, a step; nothing where the
    trace holds no collective."""
    traced = ctx["traced"]
    if traced is None or not traced["collective_ops"]:
        return None
    return 1e3 * traced[key] / traced["steps"]
