"""Global runtime state and lifecycle: init / shutdown / rank queries.

TPU-native analog of the reference's core runtime entry points
(reference: horovod/common/operations.cc — horovod_init /
InitializeHorovodOnce / horovod_rank / horovod_size ...; state struct in
horovod/common/global_state.h — HorovodGlobalState).

Bootstrap maps the reference's MPI/Gloo rendezvous onto the JAX
coordination service: the launcher provides HOROVOD_COORDINATOR_ADDR and
rank/size env, and init() calls jax.distributed.initialize() — which is
rendezvous + KV store + heartbeat/failure detection in one
(reference analog: horovod/common/gloo/gloo_context.cc HTTPStore
rendezvous against the launcher's RendezvousServer).
"""

from __future__ import annotations

import atexit
import os
import threading
import time
from typing import Any, Dict, Optional

import jax

from . import logging as hlog
from .config import Config
from .topology import Topology, detect
from ..tracing import host_span


class HorovodTpuState:
    """Singleton runtime state (reference: HorovodGlobalState)."""

    def __init__(self):
        self.initialized = False
        self.config: Optional[Config] = None
        self.topology: Optional[Topology] = None
        self.process_set_table = None   # built by ops.process_set at init
        self.engine = None              # eager fusion engine (ops.engine)
        self.timeline = None            # timeline.Timeline when enabled
        self.autotuner = None
        self.metrics_server = None      # metrics.MetricsServer when enabled
        self.metrics_summary = None     # metrics.SummaryLogger (rank 0)
        self.elastic_enabled = False
        self._lock = threading.Lock()
        self._owns_distributed = False


_state = HorovodTpuState()


def _ensure_distributed(cfg: Config) -> bool:
    """Bring up the JAX coordination service when launched multi-process.

    Returns True if this call performed jax.distributed.initialize().
    """
    if cfg.coordinator_addr and cfg.size > 1:
        from .config import env_value
        # See the HOROVOD_SHUTDOWN_BARRIER_TIMEOUT knob doc: 0 = auto
        # (60 under the elastic launcher, jax's 300 otherwise).
        shutdown_timeout = int(cfg.shutdown_barrier_timeout) or (
            60 if env_value("HOROVOD_ELASTIC") else 300)
        kwargs = dict(
            coordinator_address=cfg.coordinator_addr,
            num_processes=cfg.size,
            process_id=max(cfg.rank, 0),
            initialization_timeout=int(max(cfg.start_timeout, 1)),
            shutdown_timeout_seconds=shutdown_timeout,
        )
        # An earlier single-process incarnation (an elastic world that
        # shrank to one rank) leaves a live backend behind, and JAX
        # refuses to join a coordination service once one exists.
        import jax.extend.backend as _xb
        _xb.clear_backends()
        try:
            jax.distributed.initialize(**kwargs)
        except Exception:
            # A FAILED initialize can leave jax's global distributed
            # state partially set (service bound, client half
            # connected); without this teardown every retry would die
            # on "initialize should only be called once".
            try:
                jax.distributed.shutdown()
            except Exception as e2:  # pragma: no cover - best effort
                hlog.debug("post-failure distributed teardown: %s", e2)
            raise
        return True
    return False


def _seconds_since_process_start() -> Optional[float]:
    """Seconds since the kernel created this process (Linux: field 22
    of /proc/self/stat against CLOCK_BOOTTIME, 10 ms fine), or None
    where the platform does not say."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        return (time.clock_gettime(time.CLOCK_BOOTTIME)
                - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return None


def init(config_overrides: Optional[Dict[str, Any]] = None,
         process_sets: Optional[list] = None) -> None:
    """Initialize horovod_tpu. Idempotent (reference: InitializeHorovodOnce).

    Args:
      config_overrides: programmatic overrides for any HOROVOD_* knob.
      process_sets: optional list of ProcessSet objects to register at
        init, mirroring hvd.init(process_sets=...).
    """
    started_after = _seconds_since_process_start()
    with _state._lock:
        if _state.initialized:
            return
        from ..metrics import REGISTRY as _registry
        if started_after is not None and _registry.get(
                "hvd_init_started_after_seconds") is None:
            # Once a process: what precedes the first init() is the
            # imports and whatever brought the runtime up before it.
            _registry.gauge(
                "hvd_init_started_after_seconds",
                "Seconds from the process's creation to the first "
                "line of its first hvd.init(): interpreter start, "
                "imports, and any use of JAX before init."
            ).set(started_after)
        with host_span("init"):
            cfg = Config(config_overrides)
            _state.config = cfg
            hlog.configure(cfg.log_level, cfg.log_timestamp,
                           cfg.log_rank0_only)
            # Fail fast on bad knob values BEFORE any threads/sockets/
            # backends exist — a raise later would leak a live engine
            # because shutdown() early-returns while !initialized.
            if cfg["HOROVOD_CPU_OPERATIONS"] != "xla":
                raise ValueError(
                    f"HOROVOD_CPU_OPERATIONS="
                    f"{cfg['HOROVOD_CPU_OPERATIONS']!r} is not supported: "
                    f"the data plane is always XLA collectives ('xla'); "
                    f"there is no gloo/mpi CPU path here")
            from ..ops import dispatch as _dispatch
            _dispatch.set_alltoall_mode(cfg.alltoall_mode)
            _dispatch.set_span_devices(cfg.eager_span_devices)
            from ..ops import adasum as _adasum
            _adasum.set_adasum_mode(cfg.adasum_mode)
            with host_span("init.distributed"):
                _state._owns_distributed = _ensure_distributed(cfg)
            with host_span("init.topology"):
                _state.topology = detect(cfg)
            if _state._owns_distributed:
                from .topology import exchange_process_indices
                with host_span("init.distributed"):
                    exchange_process_indices(
                        _state.topology.rank, _state.topology.size,
                        max(cfg.start_timeout, 1))
            hlog.set_rank(_state.topology.rank)
            # Launch profile AFTER topology detection: the alltoall auto
            # heuristic's inputs must be IDENTICAL on every rank
            # (divergent ragged-vs-padded choices for the same collective
            # deadlock the gang), so the per-process launch measurement
            # only runs single-process — and the guard must see the TRUE
            # world size (launcher-less worlds have cfg.size == -1 but
            # jax.process_count() > 1). Multi-process worlds use the
            # pinned knob (the launcher forwards env uniformly) or a
            # deterministic default.
            if cfg.launch_overhead_us >= 0:
                overhead = cfg.launch_overhead_us / 1e6
            elif _state.topology.size > 1:
                overhead = 100e-6
            else:
                overhead = None  # lazy single-process measurement
            _dispatch.set_launch_profile(
                overhead_s=overhead,
                bytes_per_s=cfg.wire_bytes_per_sec,
                max_rounds=cfg.alltoall_max_rounds)
            with host_span("init.engine"):
                _init_engine(cfg, process_sets)
            with host_span("init.observability"):
                _init_observability(cfg)
                _state.initialized = True

                # Tracing wiring LAST (the clock-calibration address
                # broadcast is a collective, so the controller must already
                # be live): SIGUSR2 flight-recorder dumps + the NTP-style
                # offset estimation against rank 0 that makes per-rank
                # timelines mergeable. Best-effort — never fails init.
                from .. import tracing as _tracing
                _tracing.on_init(cfg, _state)

                # Lifecycle journal AFTER tracing: it persists the
                # calibrated clock offset (when one exists) so driver+worker
                # journals merge on one timeline. Best-effort like tracing.
                from .. import journal as _journal
                _journal.on_init(cfg, _state)

                # Health telemetry LAST: it samples the metrics the layers
                # above register, and its first beat should see an
                # initialized world. Best-effort like the journal.
                from .. import telemetry as _telemetry
                _telemetry.on_init(cfg, _state)

            hlog.info("horovod_tpu initialized: rank=%d size=%d local_rank=%d "
                      "local_size=%d cross_rank=%d cross_size=%d devices=%d",
                      _state.topology.rank, _state.topology.size,
                      _state.topology.local_rank, _state.topology.local_size,
                      _state.topology.cross_rank, _state.topology.cross_size,
                      jax.local_device_count())


def _init_engine(cfg: Config, process_sets: Optional[list]) -> None:
    """init()'s `init.engine` phase: what collectives run on."""
    # Process-set table (global set at slot 0), built lazily here to
    # avoid import cycles.
    from ..ops.process_set import ProcessSetTable
    _state.process_set_table = ProcessSetTable(_state.topology)
    if process_sets:
        for ps in process_sets:
            _state.process_set_table.register(ps)

    # Eager engine (queue + fusion + negotiation). Cheap to create;
    # spawns its background thread on first eager enqueue.
    from ..ops.engine import Engine
    _state.engine = Engine(cfg, _state.topology,
                           _state.process_set_table)

    # Negotiated-cycle controller: ON whenever ranks could submit
    # out of order (size > 1) — the reference's core value
    # proposition — or when forced for tests. 'inline' disables
    # (single-process fast path keeps inline dispatch).
    mode = (cfg.controller or "auto").lower()
    want = {"auto": _state.topology.size > 1,
            "native": True, "python": True,
            "inline": False, "none": False}.get(mode, False)
    if want:
        from ..ops.controller import (NegotiatedController,
                                      PythonCore)
        forced_python = mode == "python"
        core = (PythonCore(cfg.fusion_threshold, cfg.cycle_time_ms)
                if forced_python and _state.topology.size == 1
                else None)
        _state.engine.controller = NegotiatedController(
            cfg, _state.topology, _state.engine, core=core)

    if cfg.autotune:
        from ..autotune import Autotuner
        _state.autotuner = Autotuner(cfg)
        _state.engine.attach_autotuner(_state.autotuner)

    # Hierarchical allreduce (reference: HOROVOD_HIERARCHICAL_
    # ALLREDUCE / NCCLHierarchicalAllreduce): factor the process
    # axis as (slice over DCN) x (chip-within-slice over ICI)
    # using the launcher-detected local_size.
    from ..ops import dispatch as _dispatch
    _dispatch.set_hierarchical(
        _state.topology.local_size
        if cfg.hierarchical_allreduce else 0)


def _init_observability(cfg: Config) -> None:
    """The local part of init()'s `init.observability` phase; the
    hooks that may run a collective stay in init() itself, after
    `initialized` is set."""
    if cfg.timeline_path:
        # EVERY rank records a trace (the merge + straggler
        # attribution needs all of them): rank 0 keeps the
        # configured path verbatim (reference compatibility),
        # rank N writes a .rankN sibling the merge discovers.
        # Observability must never kill training: a host where
        # the trace directory is missing/unwritable loses THAT
        # rank's trace with a warning, not the whole job (rank 0
        # alone opened the file before this build, so such
        # worker hosts were previously valid).
        from ..timeline import Timeline
        r = _state.topology.rank
        try:
            _state.timeline = Timeline(
                Timeline.rank_path(cfg.timeline_path, r),
                mark_cycles=cfg.timeline_mark_cycles, rank=r)
            _state.engine.attach_timeline(_state.timeline)
        except OSError as e:
            hlog.warning("timeline: cannot open %s (%s); this "
                         "rank records no trace",
                         Timeline.rank_path(cfg.timeline_path, r),
                         e)

    # Metrics: the registry is always on (every subsystem above
    # already instruments against it); the scrape endpoint and the
    # rank-0 summary heartbeat are opt-in.
    from ..metrics import REGISTRY as _registry
    from ..metrics import MetricsServer, SummaryLogger
    _registry.gauge("hvd_rank",
                    "This process's world rank.").set(
        _state.topology.rank)
    _registry.gauge("hvd_world_size",
                    "Number of processes in the world.").set(
        _state.topology.size)
    if cfg.metrics_port:
        port = int(cfg.metrics_port) + max(
            _state.topology.local_rank, 0)
        try:
            _state.metrics_server = MetricsServer(port)
            hlog.info("metrics: serving Prometheus text on "
                      ":%d/metrics", _state.metrics_server.port)
        except (OSError, OverflowError) as e:
            # Observability must never kill training: warn and run
            # registry-only. OverflowError covers an out-of-range
            # port (e.g. base + local_rank past 65535) — the bind
            # raises it instead of OSError.
            hlog.warning("metrics: could not bind port %d (%s); "
                         "scrape endpoint disabled", port, e)
    if cfg.metrics_summary_seconds > 0 and _state.topology.rank == 0:
        _state.metrics_summary = SummaryLogger(
            cfg.metrics_summary_seconds)


def shutdown() -> None:
    """Tear down the engine and (if we started it) the coordination
    service (reference: horovod_shutdown in operations.cc)."""
    with _state._lock:
        if not _state.initialized:
            return
        if _state.engine is not None:
            _state.engine.shutdown()
            _state.engine = None
        if _state.timeline is not None:
            _state.timeline.close()
            _state.timeline = None
        from .. import tracing as _tracing
        _tracing.on_shutdown()
        if _state.metrics_summary is not None:
            _state.metrics_summary.stop()
            _state.metrics_summary = None
        if _state.metrics_server is not None:
            _state.metrics_server.stop()
            _state.metrics_server = None
        if _state._owns_distributed:
            try:
                jax.distributed.shutdown()
            except Exception as e:  # pragma: no cover - best effort
                hlog.debug("jax.distributed.shutdown failed: %s", e)
            _state._owns_distributed = False
            # Elastic re-init may come back with a DIFFERENT world
            # size/coordinator: drop the cached PJRT backends so the
            # next init() rebuilds the device view.
            try:
                import jax.extend.backend as _xb
                _xb.clear_backends()
            except Exception as e:  # pragma: no cover
                hlog.debug("clear_backends failed: %s", e)
        _state.initialized = False
        _state.process_set_table = None
        _state.topology = None
        from .topology import reset_process_indices
        reset_process_indices()
        from ..ops import dispatch as _dispatch
        _dispatch.set_hierarchical(0)
        _dispatch.set_alltoall_mode("auto")
        _dispatch.set_span_devices("auto")
        _dispatch.set_launch_profile(None, 4e10, 16)
        from ..ops import adasum as _adasum
        _adasum.set_adasum_mode("auto")


atexit.register(shutdown)


def _require_init() -> HorovodTpuState:
    if not _state.initialized:
        raise RuntimeError(
            "horovod_tpu has not been initialized; call hvd.init() first.")
    return _state


def state() -> HorovodTpuState:
    return _state


def is_initialized() -> bool:
    return _state.initialized


def rank() -> int:
    return _require_init().topology.rank


def size() -> int:
    return _require_init().topology.size


def local_rank() -> int:
    return _require_init().topology.local_rank


def local_size() -> int:
    return _require_init().topology.local_size


def cross_rank() -> int:
    return _require_init().topology.cross_rank


def cross_size() -> int:
    return _require_init().topology.cross_size


def is_homogeneous() -> bool:
    return _require_init().topology.is_homogeneous


def start_timeline(file_path: str, mark_cycles: bool = False) -> None:
    """Runtime timeline start (reference: TimelineController). Every
    rank records — rank 0 at `file_path` verbatim, rank N at a
    `.rankN` sibling — so `hvdrun --timeline-merge` can fuse them.
    Cross-host clock CALIBRATION only comes up when HOROVOD_TIMELINE
    was set at init (its address broadcast cannot safely run
    mid-training); a runtime-started trace rebinds an existing
    calibrator, else merges on raw monotonic anchors (same-host
    only — merge() warns)."""
    st = _require_init()
    if st.timeline is not None:
        st.timeline.close()
    from .. import tracing as _tracing
    from ..timeline import Timeline
    r = st.topology.rank
    st.timeline = Timeline(Timeline.rank_path(file_path, r),
                           mark_cycles=mark_cycles, rank=r)
    st.engine.attach_timeline(st.timeline)
    _tracing.rebind_timeline(st.timeline)


def stop_timeline() -> None:
    st = _require_init()
    if st.timeline is not None:
        st.timeline.close()
        st.timeline = None
        st.engine.attach_timeline(None)
        from .. import tracing as _tracing
        _tracing.rebind_timeline(None)
