"""Explicit data-parallel training with error-feedback int8 gradient
compression — the cross-pod reduce trick from DESIGN.md §3, demonstrated
on 8 fake devices.

    PYTHONPATH=src python examples/compressed_dp.py
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax                                                   # noqa: E402
from jax.sharding import PartitionSpec as P                  # noqa: E402

from jax.experimental.shard_map import shard_map             # noqa: E402

from repro.configs import get_reduced                        # noqa: E402
from repro.distributed import compression as C               # noqa: E402
from repro.launch.mesh import make_host_mesh                 # noqa: E402
from repro.models.lm import Model                            # noqa: E402
from repro.train.data import DataConfig, SyntheticLM         # noqa: E402
from repro.train.optimizer import OptConfig, init, update    # noqa: E402

mesh = make_host_mesh(n_data=8, n_model=1)
cfg = get_reduced("demo-100m")
model = Model(cfg)
params = model.init(jax.random.PRNGKey(0))
ocfg = OptConfig(lr=1e-2, warmup_steps=2, total_steps=60)
opt = init(ocfg, params)
residual = C.zero_residual(params)
data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=32,
                              global_batch=16))


def local_grads(params, batch):
    """Per-shard loss/grad + EF-int8 all-reduce over the data axis."""

    def f(p, b, r):
        loss, g = jax.value_and_grad(model.loss_fn)(p, b)
        red, new_r = C.ef_int8_reduce(g, r, "data")
        loss = jax.lax.pmean(loss, "data")
        return loss, red, new_r

    return shard_map(
        f, mesh=mesh,
        in_specs=(P(), P("data"), P()),
        out_specs=(P(), P(), P()))(params, batch, residual)


@jax.jit
def step(params, opt, residual, batch):
    loss, grads, residual = local_grads(params, batch)
    params, opt, m = update(ocfg, grads, opt, params)
    return params, opt, residual, loss


losses = []
for i in range(40):
    batch = data.batch(i)
    params, opt, residual, loss = step(params, opt, residual, batch)
    losses.append(float(loss))
    if i % 10 == 0:
        print(f"step {i} loss {losses[-1]:.4f} (int8-compressed reduce)")
print(f"loss {losses[0]:.3f} -> {losses[-1]:.3f} with 4x smaller "
      f"gradient payloads")
assert losses[-1] < losses[0]
