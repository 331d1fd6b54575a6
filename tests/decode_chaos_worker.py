"""Decode-chaos pool member: a plain-subprocess remote decode worker
for the mid-SEQUENCE kill tests in tests/test_decoding.py and the
bench chaos leg (bench.py --serving decode leg).

It joins the DecodeFrontend living in the LAUNCHING process over the
HMAC-signed lease/emit wire (decoding.remote_decode_loop) and decodes
until the frontend says stop. A seeded HOROVOD_FAULTS=
decode.step:crash:... arms from env and is a REAL os._exit(43)
mid-sequence — the process dies with its KV cache and partially
emitted streams, which is exactly what the per-sequence watermark
resume has to survive.

Env contract (set by the launcher): DECODE_TEST_ADDR /
DECODE_TEST_PORT (the frontend endpoint), DECODE_TEST_SECRET (the
endpoint's HMAC key), DECODE_TEST_WID (worker id; defaults to the
pid). The toy LM is the decoding module's default, deterministic in
its seed, so the frontend-side uninterrupted baseline is bitwise
comparable.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from horovod_tpu import decoding, faults  # noqa: E402


def main():
    faults.configure_from_env()
    wid = os.environ.get("DECODE_TEST_WID", f"pid{os.getpid()}")
    n = decoding.remote_decode_loop(
        os.environ["DECODE_TEST_ADDR"],
        int(os.environ["DECODE_TEST_PORT"]),
        wid=wid,
        secret=os.environ.get("DECODE_TEST_SECRET", ""))
    print(f"decode worker {wid}: finished {n} sequences", flush=True)


if __name__ == "__main__":
    main()
