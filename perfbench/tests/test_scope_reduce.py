"""`scope_reduce` on hand-made events, and on small recorded v5e
traces of this PR's program (`data/*.scopes.xplane.pb.gz`, cut down by
`cut_scopes.py`, which keeps the one statistic the reader needs)."""

import glob
import gzip
import os

import pytest

from perfbench import scope_readers, scope_reduce as sr, trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
LAYER = "jit(local_step)/transpose(jvp())/while/body/closed_call/checkpoint"


@pytest.mark.parametrize("op_name, want", [
    ("jit(local_step)/jvp()/while/body/closed_call/hvd.ffn/dot_general:",
     ("hvd.ffn", "forward")),
    (LAYER + "/hvd.attn.core/mul:", ("hvd.attn.core", "backward")),
    (LAYER + "/rematted_computation/hvd.attn.proj/dot_general:",
     ("hvd.attn.proj", "recompute")),
    ("jit(local_step)/shard_map/transpose(jvp(hvd.grad_reduce.b12))/"
     "psum_invariant:", ("hvd.grad_reduce.b12", "backward")),
    ("jit(step)/jvp(hvd.head_loss)/exp", ("hvd.head_loss", "forward")),
    # nested scopes: the innermost, which is the last, wins
    ("jit(step)/hvd.moe/hvd.attn.core/dot_general",
     ("hvd.attn.core", "forward")),
    ("jit(local_step)/transpose(jvp())/while/body/squeeze:",
     ("unscoped", "backward")),
    ("jit(f)/checkpoint/rematted_computation/mul",
     ("unscoped", "recompute")),
    ("", ("unscoped", "forward")),
])
def test_scope_and_pass(op_name, want):
    assert sr.scope_and_pass(op_name) == want


def hand_made():
    """Two steps; a `while`, unnamed as the chip's trace leaves it,
    holds two backward instructions; one other instruction has no
    name, one runs after the window."""
    loop = "%while.9 = (s32[]) while((s32[]) %t), condition=%c, body=%b"
    reduce_b0 = "%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %x)"
    reduce_b1 = "%all-reduce.2 = f32[8]{0} all-reduce(f32[8]{0} %y)"
    names = {
        "%fusion.1": "jit(s)/jvp()/while/body/hvd.ffn/dot_general:",
        "%fusion.2": "jit(s)/transpose(jvp())/while/body/checkpoint/"
                     "rematted_computation/hvd.ffn/dot_general:",
        "%fusion.3": "jit(s)/transpose(jvp())/while/body/checkpoint/"
                     "hvd.ffn/dot_general:",
        reduce_b0: "jit(s)/transpose(jvp(hvd.grad_reduce.b0))/psum:",
        reduce_b1: "jit(s)/transpose(jvp(hvd.grad_reduce.b1))/psum:",
        "%fusion.4": "jit(s)/hvd.optimizer/add:",
    }
    ops = []
    for base in (0, 1000):
        ops += [("%fusion.1", base + 100, base + 200),
                (loop, base + 200, base + 600),
                ("%fusion.2", base + 210, base + 300),
                (reduce_b1, base + 300, base + 350),
                ("%fusion.3", base + 350, base + 590),
                (reduce_b0, base + 650, base + 700),
                ("%copy.5", base + 700, base + 707),
                ("%fusion.4", base + 800, base + 900)]
    ops.append(("%fusion.4", 2100, 2200))
    return [(0, 1000), (1000, 2000)], ops, names


def test_parts_add_up_to_busy_to_the_picosecond():
    steps, ops, names = hand_made()
    got = sr.reduce(steps, ops, names)
    busy = tr.length(tr.union([(s, e) for _, s, e in tr.clip(
        ops, (0, 2000))]))
    assert got["steps"] == 2 and got["busy_ps"] == busy == 2 * 657
    assert got["by"] == {
        ("hvd.ffn", "forward"): 200, ("hvd.ffn", "recompute"): 180,
        ("hvd.ffn", "backward"): 480,
        ("hvd.grad_reduce.b1", "backward"): 100,
        ("hvd.grad_reduce.b0", "backward"): 100,
        ("unscoped", "forward"): 14 + 2 * (400 - 90 - 50 - 240),
        ("hvd.optimizer", "forward"): 200}
    # bucket 1 starts inside the backward scan, bucket 0 after it
    assert got["bucket_start_ms"] == {
        "hvd.grad_reduce.b0": 50e-9, "hvd.grad_reduce.b1": -300e-9}
    assert sr.ms_a_step(got, lambda s: s == "hvd.ffn") == \
        pytest.approx(430e-9)
    assert sr.ms_a_step(got, passes=("recompute",)) == \
        pytest.approx(90e-9)
    assert sr.ms_a_step(got, lambda s: s == "hvd.moe") is None
    rows = sr.table(got)["scopes"]
    assert sum(r["pct_of_busy"] for r in rows.values()) == \
        pytest.approx(100.0)


def test_a_program_without_names_gives_nothing():
    steps, ops, names = hand_made()
    assert sr.reduce(steps, ops, {}) is None
    assert sr.reduce(steps, ops, dict.fromkeys(
        names, "jit(s)/transpose(jvp())/while/body/mul:")) is None
    assert sr.reduce([], ops, names) is None
    assert sr.reduce(steps, [], names) is None


def test_readers_return_nothing_without_a_trace_or_a_counter(tmp_path):
    assert scope_readers.scope_ms({"traced": None}, "hvd.ffn") is None
    assert scope_readers.unscoped_pct({"traced": None}) is None
    assert scope_readers.counter("hvd_no_such_counter_total") is None
    assert sr.newest(str(tmp_path)) is None


def unpack(packed, tmp_path, cell):
    """A fixture laid out as a run leaves its trace under `out/`."""
    folder = tmp_path / "out" / "trace" / cell / "plugins" / "profile" / "t"
    folder.mkdir(parents=True)
    path = str(folder / "trace.xplane.pb")
    with gzip.open(packed) as src, open(path, "wb") as dst:
        dst.write(src.read())
    return path


@pytest.mark.parametrize("packed", sorted(glob.glob(
    os.path.join(DATA, "*.scopes.xplane.pb.gz"))), ids=os.path.basename)
def test_recorded_trace(packed, tmp_path, capsys, monkeypatch):
    """`<cell>.scopes.xplane.pb.gz`: the first chip's plane of a trace
    of this PR's program on the v5e."""
    cell = os.path.basename(packed).split(".scopes.")[0]
    path = unpack(packed, tmp_path, cell)
    monkeypatch.setattr(sr, "HERE", str(tmp_path))
    assert sr.newest_trace() == path
    got = sr.newest()
    assert got is sr.newest()        # read once
    assert '"phase": "scopes"' in capsys.readouterr().out
    whole = tr.reduce_file(path, 1)
    assert got["steps"] == whole["steps"] == 2
    assert got["busy_ps"] == round(whole["busy_first_chip_s"] * tr.PS)
    scopes = {scope for scope, _ in got["by"]}
    assert {"hvd.embed", "hvd.attn.proj", "hvd.attn.core", "hvd.ffn",
            "hvd.head_loss", "hvd.optimizer", "unscoped"} <= scopes
    for scope in ("hvd.attn.proj", "hvd.attn.core", "hvd.ffn"):
        assert all(got["by"][scope, p] > 0 for p in sr.PASSES)
    unscoped = sum(ps for (scope, _), ps in got["by"].items()
                   if scope == "unscoped")
    assert unscoped < 0.10 * got["busy_ps"]
    ctx = {"traced": whole}
    parts = [scope_readers.scope_ms(ctx, scope) for scope in scopes]
    assert sum(parts) == pytest.approx(
        1e3 * whole["busy_first_chip_s"] / 2, rel=1e-12)
    buckets = {s for s in scopes if sr.BUCKET.match(s)}
    _, ops, names = sr.read_trace(path)
    collectives = {text for text, _, _ in ops
                   if tr.COLLECTIVE.match(tr.instruction(text)[1])}
    assert len(collectives) == len(whole["collective_ops"])
    if "dp4" in cell:
        # every traced all-reduce carries one bucket's name
        assert {sr.scope_and_pass(names[text])[0]
                for text in collectives} == buckets
        assert set(got["bucket_start_ms"]) == buckets
        assert scope_readers.grad_reduce_ms(ctx) >= \
            1e3 * whole["collective_s"] / 2
    else:
        assert not buckets and not collectives
        assert scope_readers.grad_reduce_ms(ctx) is None
