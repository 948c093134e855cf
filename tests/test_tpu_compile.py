"""Compile START's device programs for a described TPU v5e.

No chip is attached here: the TPU compiler builds each program for a
``v5e:2x2`` topology it is only told about, at the paper's deployment
width (Table 4: 400 hosts, ``max_tasks=10``).  What the chip's compiler
would refuse — a Mosaic kernel that cannot lower, an unaligned slice, a
program that does not fit — fails here at no chip time.  Nothing runs,
so these tests say nothing about results or speed (``chip_smoke.py``
covers those on the chip).

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and every test worker
imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import encoder_lstm as net
from repro.core import features
from repro.core.predictor import (_N_SCALARS, _fused_step,
                                  _fused_step_tenants, _slot_write,
                                  tenant_layout)
from repro.kernels.lstm_cell.lstm_cell import lstm_cell_pallas

N_HOSTS, MAX_TASKS, HORIZON, SLOTS = 400, 10, 5, 64
HOST_DIM = N_HOSTS * features.HOST_FEATURES
TASK_DIM = MAX_TASKS * features.TASK_FEATURES
INPUT_DIM = features.input_dim(N_HOSTS, MAX_TASKS)


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the
    # persistent cache but cannot be read back without one
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


def _sds(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _params(sharding):
    return _sds(jax.eval_shape(
        lambda: net.init_params(jax.random.PRNGKey(0), INPUT_DIM)), sharding)


def _f32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("batch", [8, 128])
def test_lstm_cell_kernel_compiles_to_mosaic(one_chip, batch):
    args = [_f32(s, one_chip) for s in
            [(batch, 32), (batch, 32), (batch, 32), (32, 128), (32, 128),
             (128,)]]
    compiled = jax.jit(lambda *a: lstm_cell_pallas(
        *a, block_b=min(batch, 128), interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("per_task", [False, True])
@pytest.mark.parametrize("nb", [1, 16])
def test_fused_step_compiles_at_table4_width(one_chip, nb, per_task,
                                             use_pallas):
    packed = _N_SCALARS + HOST_DIM + nb * (1 + TASK_DIM)
    compiled = _fused_step.lower(
        _params(one_chip), _f32((HORIZON, HOST_DIM), one_chip),
        _f32((packed,), one_chip), nb=nb, task_dim=TASK_DIM,
        use_pallas=use_pallas, per_task=per_task, unroll=2).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == use_pallas
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 16 << 20


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("nb", [2048, 4096])
def test_tenant_step_compiles_at_service_width(one_chip, nb, use_pallas):
    """The service's tenant step at the 64-tenant deployment: 64 slots of
    400-host rings, the job rows of a tick up to 4096."""
    slab = _f32((SLOTS, HORIZON, HOST_DIM), one_chip)
    size = tenant_layout(SLOTS, nb, HOST_DIM, TASK_DIM)[-1]
    compiled = _fused_step_tenants.lower(
        _params(one_chip), slab, _f32((size,), one_chip), nb=nb,
        task_dim=TASK_DIM, use_pallas=use_pallas, unroll=2).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == use_pallas
    assert compiled.memory_analysis().argument_size_in_bytes < 16 << 20
    _slot_write.lower(slab, _f32((1 + HORIZON * HOST_DIM,),
                                 one_chip)).compile()


def test_train_step_compiles_at_table4_width(one_chip):
    params = _params(one_chip)
    opt = _sds(jax.eval_shape(net.adam_init, params), one_chip)
    compiled = net.train_step.lower(
        params, opt, _f32((HORIZON, 64, INPUT_DIM), one_chip),
        _f32((64, 2), one_chip), lr=1e-3).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
