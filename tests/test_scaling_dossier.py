"""Round-9 committed-artifact consistency: the steady-state composed
timeline (benchmarks/TIMELINE_steady_2proc_r09.json) is a CLAIM the
repo ships — this test keeps it honest against drift."""

import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEADY = os.path.join(REPO, "benchmarks",
                      "TIMELINE_steady_2proc_r09.json")


def test_steady_timeline_claims():
    """The round-9 steady-state composed artifact's headline
    (VERDICT 'What's missing' 1): NEGOTIATE p50 below the cycle
    budget once the compile cycle is excluded, both ranks present,
    provenance stated."""
    with open(STEADY) as f:
        doc = json.load(f)
    neg = doc["metadata"]["negotiate_ms"]
    assert neg["steady_p50"] < neg["cycle_budget_ms"]
    assert neg["steady_p95"] < neg["cycle_budget_ms"]
    prov = doc["metadata"]["provenance"]
    assert prov["compile_cycles_excluded"] == [0]
    assert doc["metadata"]["ranks"] == [0, 1]
    # The spans the claim is computed from are really in the trace.
    neg_ends = [e for e in doc["traceEvents"]
                if e.get("name") == "NEGOTIATE"
                and e.get("ph") == "E"
                and "coordinator_negotiate_us" in e.get("args", {})]
    steady = [e for e in neg_ends if e["args"].get("step", 0) > 0]
    assert len(steady) >= neg["steady_count"] // 2
