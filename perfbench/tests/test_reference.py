"""The system agrees with the plain references at a tiny size on the
CPU, and the tolerances catch a lower precision and a dropped term."""

import os

import pytest

from perfbench import run
from perfbench.tests.test_harness import ROOT, TINY


def fp8_weights(loss_fn):
    """The system with every matrix rounded to fp8's (e4m3) 3 bits of
    mantissa first. `reduce_precision`, because the TPU compiler drops
    a cast to a narrower type and back."""
    import jax

    def rounded(a):
        if a.ndim < 2:
            return a
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=3)
    return lambda p, b: loss_fn(jax.tree.map(rounded, p), b)


def doubled_norm_gains(loss_fn):
    """The system with a term dropped: every norm gain doubled, which
    is what forgetting a normalisation's scale looks like."""
    import jax

    def doubled(path, a):
        name = jax.tree_util.keystr(path)
        return a * 2 if "norm" in name.lower() and "bias" not in name \
            else a
    return lambda p, b: loss_fn(
        jax.tree_util.tree_map_with_path(doubled, p), b)


FAULTS = (None, fp8_weights, doubled_norm_gains)


# No ("resnet", fp8_weights): BatchNorm after every convolution hides
# rounded weights from a loss and a gradient norm, on the chip too
# (reference/resnet.py).
@pytest.mark.parametrize("family,fault,agrees", [
    ("transformer", None, True), ("transformer", fp8_weights, False),
    ("transformer", doubled_norm_gains, False),
    ("resnet", None, True), ("resnet", doubled_norm_gains, False)])
def test_system_against_reference(family, fault, agrees):
    import jax
    from horovod_tpu.parallel.mesh import data_parallel_mesh

    driver = run.load_module(ROOT, "drivers", "jit_train")
    model = run.load_module(ROOT, "models", family)
    reference = run.load_module(ROOT, "reference", family)
    config, spec = TINY[family]["config"], TINY[family]["cell"]
    mesh = data_parallel_mesh(jax.devices()[:2])
    m = model.build(config, spec, 2)
    if fault is not None:
        m.loss_fn = fault(m.loss_fn)
    params, carry, sample = driver.weights_and_sample(
        m, mesh, *jax.random.split(driver.seed_key(7)))
    assert driver.sample_check(m, reference, config, mesh, params, carry,
                               sample) is agrees
