"""Worker for the torch-frontend launcher test: exercises
`import horovod_tpu.torch as hvd` across REAL processes (the
reference analog: horovodrun -np 2 pytest test_torch.py,
SURVEY.md §4 tier 1)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import torch  # noqa: E402

import horovod_tpu.torch as hvd  # noqa: E402


def main():
    hvd.init()
    r, n = hvd.rank(), hvd.size()
    print(f"torch worker rank={r} size={n}")

    # allreduce average of rank-dependent tensors
    out = hvd.allreduce(torch.full((4,), float(r + 1)), name="t0")
    np.testing.assert_allclose(out.numpy(),
                               np.full(4, sum(range(1, n + 1)) / n))

    # in-place sum
    t = torch.full((3,), float(r))
    hvd.allreduce_(t, op=hvd.Sum, name="t1")
    np.testing.assert_allclose(t.numpy(), np.full(3, sum(range(n))))

    # bf16 wire, dtype preserved
    out = hvd.allreduce(torch.ones(8, dtype=torch.bfloat16),
                        op=hvd.Sum, name="t2")
    assert out.dtype == torch.bfloat16
    np.testing.assert_allclose(out.float().numpy(), float(n))

    # uneven allgather
    out = hvd.allgather(torch.full((r + 1, 2), float(r)), name="t3")
    want = np.concatenate(
        [np.full((i + 1, 2), float(i)) for i in range(n)])
    np.testing.assert_allclose(out.numpy(), want)

    # broadcast_parameters: every rank converges to rank 0's weights
    torch.manual_seed(100 + r)   # deliberately different per rank
    model = torch.nn.Linear(3, 2)
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)
    gathered = hvd.allgather(model.weight.detach().reshape(1, -1),
                             name="t4")
    for i in range(1, n):
        np.testing.assert_allclose(gathered[i].numpy(),
                                   gathered[0].numpy())

    # hook-based DistributedOptimizer: rank-dependent batches, grads
    # averaged across ranks => identical post-step weights everywhere
    opt = hvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.5),
        named_parameters=model.named_parameters())
    X = torch.full((8, 3), float(r + 1))
    Y = torch.zeros(8, 2)
    for _ in range(3):
        opt.zero_grad()
        loss = torch.nn.functional.mse_loss(model(X), Y)
        loss.backward()
        opt.step()
    gathered = hvd.allgather(model.weight.detach().reshape(1, -1),
                             name="t5")
    for i in range(1, n):
        np.testing.assert_allclose(gathered[i].numpy(),
                                   gathered[0].numpy(), rtol=1e-6)

    # sparse allreduce over torch sparse COO (rank-dependent nnz)
    if r == 0:
        s = torch.sparse_coo_tensor(torch.zeros((1, 0), dtype=torch.long),
                                    torch.zeros((0, 2)), size=(5, 2))
    else:
        s = torch.sparse_coo_tensor(
            torch.tensor([[1, min(r + 1, 4)]]),
            torch.full((2, 2), float(r)), size=(5, 2))
    out = hvd.sparse_allreduce(s, op=hvd.Sum, name="t6").to_dense()
    want = np.zeros((5, 2))
    for rr in range(1, n):
        want[1] += rr
        want[min(rr + 1, 4)] += rr
    np.testing.assert_allclose(out.numpy(), want)

    # optimizer-state broadcast after real steps
    hvd.broadcast_optimizer_state(opt, root_rank=0)

    # ASYMMETRIC optimizer state: root resumed (materialized Adam
    # state), workers fresh (state == {}) — the checkpoint-resume
    # case. Root's manifest drives the broadcast set, so this must
    # not deadlock, and workers must receive root's moments.
    model2 = torch.nn.Linear(2, 2)
    hvd.broadcast_parameters(model2.state_dict(), root_rank=0)
    opt2 = torch.optim.Adam(model2.parameters(), lr=0.01)
    if r == 0:
        torch.nn.functional.mse_loss(model2(torch.ones(4, 2)),
                                     torch.zeros(4, 2)).backward()
        opt2.step()
    hvd.broadcast_optimizer_state(opt2, root_rank=0)
    st2 = opt2.state_dict()["state"]
    assert st2, f"rank {r}: optimizer state empty after broadcast"
    ea = next(iter(st2.values()))["exp_avg"].reshape(1, -1)
    gathered = hvd.allgather(ea, name="t7")
    for i in range(1, n):
        np.testing.assert_allclose(gathered[i].numpy(),
                                   gathered[0].numpy())

    # dtype x op matrix through the bridge (reference analog:
    # test_torch.py's exhaustive dtype/op coverage under -np 2).
    vals = [i + 2 for i in range(n)]
    for dt in [torch.float32, torch.float16, torch.bfloat16,
               torch.int32, torch.uint8]:
        is_float = dt.is_floating_point
        ops = [(hvd.Sum, float(sum(vals))),
               (hvd.Min, float(min(vals))),
               (hvd.Max, float(max(vals))),
               (hvd.Product, float(np.prod(vals)))]
        if is_float:
            ops.append((hvd.Average, sum(vals) / n))
        for op_, want in ops:
            x = torch.full((4, 3), r + 2).to(dt)
            out = hvd.allreduce(x, op=op_, name=f"mx.{dt}.{op_}")
            assert out.dtype == dt, (out.dtype, dt)
            tol = 5e-2 if dt in (torch.bfloat16, torch.float16) else 1e-6
            np.testing.assert_allclose(
                out.to(torch.float64).numpy(), np.full((4, 3), want),
                rtol=tol)

    # SyncBatchNorm oracle: each rank holds a DIFFERENT shard (uneven
    # sizes!) of a global batch; sync-BN output + input grad on the
    # shard must equal vanilla BatchNorm run on the concatenated
    # batch (reference: test_torch.py's sync BN coverage).
    torch.manual_seed(7)
    full = torch.randn(2 * n + n * (n + 1) // 2, 3, 4)
    shard_sizes = [2 + i + 1 for i in range(n)]
    off = sum(shard_sizes[:r])
    mine = full[off:off + shard_sizes[r]].clone().requires_grad_(True)
    bn = hvd.SyncBatchNorm(3, momentum=0.2)
    y = bn(mine)
    y.sum().backward()

    ref = torch.nn.BatchNorm1d(3, momentum=0.2)
    xref = full.clone().requires_grad_(True)
    yref = ref(xref)
    yref.sum().backward()
    np.testing.assert_allclose(
        y.detach().numpy(),
        yref[off:off + shard_sizes[r]].detach().numpy(), atol=1e-5)
    np.testing.assert_allclose(
        mine.grad.numpy(),
        xref.grad[off:off + shard_sizes[r]].numpy(), atol=1e-5)
    np.testing.assert_allclose(bn.running_mean.numpy(),
                               ref.running_mean.numpy(), atol=1e-6)
    np.testing.assert_allclose(bn.running_var.numpy(),
                               ref.running_var.numpy(), atol=1e-5)
    # weight grad: LOCAL here; averaged by the optimizer like any
    # other param grad. Allreduce(Sum) of local == the oracle's.
    wg = hvd.allreduce(bn.weight.grad, op=hvd.Sum, name="t8")
    np.testing.assert_allclose(wg.numpy(), ref.weight.grad.numpy(),
                               atol=1e-4)

    hvd.barrier()
    print(f"rank {r}: TORCH FRONTEND ALL OK")
    hvd.shutdown()


if __name__ == "__main__":
    main()
