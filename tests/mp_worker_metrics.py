"""Worker for the 2-rank metrics-scrape integration test: drives real
negotiated collectives, then scrapes its OWN /metrics endpoint (the
`curl localhost:$HOROVOD_METRICS_PORT/metrics` acceptance path — rank
i serves on port + local_rank) and cross-checks the scraped Prometheus
text against the in-process hvd.metrics() snapshot."""

import os
import re
import sys
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import horovod_tpu as hvd  # noqa: E402

_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{.*\})? "
    r"(-?[0-9]+(\.[0-9]+)?([eE][+-]?[0-9]+)?|[+-]Inf|NaN)$")


def main():
    base_port = int(os.environ["HOROVOD_METRICS_PORT"])
    hvd.init()
    r, n = hvd.rank(), hvd.size()
    assert n == 2, n

    # Exercise the negotiated paths that feed the counters.
    out = hvd.allreduce(jnp.ones(1024, jnp.float32), op=hvd.Sum,
                        name="met0")
    np.testing.assert_allclose(np.asarray(out), float(n))
    hvd.grouped_allreduce([jnp.ones(16), jnp.ones(32)], op=hvd.Sum,
                          name="met1")
    hvd.allgather(jnp.full((r + 1, 2), float(r)), name="met2")
    hvd.broadcast(jnp.arange(8.0), root_rank=0, name="met3")
    hvd.barrier()

    # The endpoint each rank serves: base + local_rank.
    lr = hvd.local_rank()
    port = base_port + max(lr, 0)
    from horovod_tpu.common.basics import state
    assert state().metrics_server is not None, "no metrics server"
    assert state().metrics_server.port == port, (
        state().metrics_server.port, port)
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10) as resp:
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/plain")
        text = resp.read().decode()

    # Valid Prometheus exposition, with the acceptance metrics.
    for line in text.splitlines():
        if line and not line.startswith("#"):
            assert _SAMPLE_RE.match(line), f"bad line: {line!r}"
    assert 'hvd_allreduce_bytes_total{pset="0"}' in text, text
    assert "hvd_dispatch_latency_seconds_bucket" in text
    assert "hvd_stalled_tensors 0" in text
    assert "hvd_negotiation_latency_seconds_count" in text

    # The scrape and the in-process snapshot must agree (no ops ran
    # in between).
    snap = hvd.metrics()
    m = re.search(r'^hvd_allreduce_bytes_total\{pset="0"\} (\S+)$',
                  text, re.M)
    scraped = float(m.group(1))
    in_proc = snap["hvd_allreduce_bytes_total"][("0",)]
    assert scraped == in_proc, (scraped, in_proc)
    # 1024 f32 + (16 + 32) f64-or-f32 leaves were submitted; at least
    # the single allreduce's 4096 raw bytes must be there.
    assert in_proc >= 4096, in_proc
    assert snap["hvd_world_size"][()] == n
    assert snap["hvd_rank"][()] == r
    assert snap["hvd_fused_batches_total"][("ar",)] >= 1

    print(f"worker rank={r}: METRICS ALL OK")
    hvd.shutdown()


if __name__ == "__main__":
    main()
