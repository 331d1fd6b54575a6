"""Build/capability metadata.

Analog of the reference's horovod/metadata/ + hvd.nccl_built()/
mpi_built()/gloo_built() capability probes and `horovodrun
--check-build` (reference: horovod/runner/launch.py). On TPU the
capability matrix is about PJRT backends and the native control-plane
core, not NCCL/MPI.
"""

from __future__ import annotations



def xla_built() -> bool:
    return True


def tpu_available() -> bool:
    try:
        import jax
        return any(d.platform == "tpu" for d in jax.devices())
    except Exception:
        return False


def native_controller_built() -> bool:
    """True when the C++ control-plane core (libhvdtpu_core.so) is
    importable."""
    try:
        from .core import native
        return native.available()
    except Exception:
        return False


def torch_frontend_available() -> bool:
    """True when `import horovod_tpu.torch as hvd` would work (torch
    itself is installed). find_spec only — the probe must not pay the
    torch import."""
    return _importable("torch")


def _importable(mod: str) -> bool:
    import importlib.util
    try:
        return importlib.util.find_spec(mod) is not None
    except (ImportError, ValueError):
        return False


def flax_available() -> bool:
    return _importable("flax")


def optax_available() -> bool:
    return _importable("optax")


def orbax_available() -> bool:
    return _importable("orbax.checkpoint")


# Compatibility shims for code migrating from the reference: the data
# plane is always XLA over PJRT, never NCCL/MPI/Gloo.
def nccl_built() -> bool:
    return False


def mpi_built() -> bool:
    return False


def gloo_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def nccl_enabled() -> bool:
    return False


def mpi_enabled() -> bool:
    return False


def gloo_enabled() -> bool:
    return False


def mpi_threads_supported() -> bool:
    """Reference API shim (horovod/torch/mpi_ops.py
    mpi_threads_supported). There is no MPI: the coordination service
    and XLA runtime are thread-safe by construction, but the honest
    answer to 'is MPI multithreading supported' is that MPI is not
    present at all."""
    return False


def check_build_summary() -> str:
    import jax
    lines = ["horovod_tpu capability matrix:"]

    def mark(flag):
        return "X" if flag else " "

    lines.append(f"  [{mark(xla_built())}] XLA collectives (PJRT)")
    lines.append(f"  [{mark(tpu_available())}] TPU devices visible")
    lines.append(f"  [{mark(native_controller_built())}] native (C++) "
                 "control-plane core")
    lines.append(f"  [{mark(True)}] python control-plane fallback")
    lines.append(f"  [{mark(torch_frontend_available())}] torch "
                 "frontend binding (horovod_tpu.torch)")
    lines.append(f"  [ ] NCCL (never linked — by design)")
    lines.append(f"  [ ] MPI (never linked — by design)")
    lines.append(f"  [ ] Gloo (never linked — by design)")
    try:
        devs = jax.devices()
        lines.append(f"  devices: {[str(d) for d in devs]}")
        lines.append(f"  process count: {jax.process_count()}")
    except Exception as e:
        lines.append(f"  devices: <unavailable: {e}>")
    return "\n".join(lines)
