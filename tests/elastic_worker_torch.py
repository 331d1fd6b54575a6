"""Elastic training worker on the TORCH frontend: a toy torch
training loop under hvd.elastic.run with TorchState (reference:
test/integration elastic torch scripts), logging
(step, world) progress per rank and surviving membership changes via
commit/restore/sync over the shared elastic machinery."""

import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import torch  # noqa: E402

import horovod_tpu.torch as hvd  # noqa: E402

LOG = os.environ["ELASTIC_TEST_LOG"]
TOTAL_STEPS = int(os.environ.get("ELASTIC_TEST_STEPS", "20"))
STEP_SLEEP = float(os.environ.get("ELASTIC_TEST_SLEEP", "0.2"))


def log_line(msg):
    with open(f"{LOG}.{os.environ.get('HOROVOD_RANK', '?')}", "a") as f:
        f.write(msg + "\n")


def main():
    hvd.init()
    torch.manual_seed(0)
    model = torch.nn.Linear(2, 1)
    opt = hvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.05),
        named_parameters=model.named_parameters())
    state = hvd.elastic.TorchState(model, opt, step=0)

    @hvd.elastic.run
    def train(state):
        while state.step < TOTAL_STEPS:
            x = torch.randn(8, 2)
            y = torch.zeros(8, 1)
            opt.zero_grad()
            loss = torch.nn.functional.mse_loss(state.model(x), y)
            loss.backward()
            opt.step()
            state.step += 1
            state.commit()
            log_line(f"step {state.step} world {hvd.size()} "
                     f"rank {hvd.rank()} loss {float(loss.detach()):.4f}")
            time.sleep(STEP_SLEEP)

    train(state)
    # weights must agree across ranks at the end (the elastic loop
    # syncs on every membership change; training itself reduces
    # gradients) — allgather and compare on rank 0.
    w = hvd.allgather(state.model.weight.detach().reshape(1, -1),
                      name="final_w")
    if hvd.rank() == 0:
        import numpy as np
        for i in range(1, hvd.size()):
            np.testing.assert_allclose(w[i].numpy(), w[0].numpy(),
                                       rtol=1e-6)
    log_line("done")
    hvd.shutdown()


if __name__ == "__main__":
    main()
