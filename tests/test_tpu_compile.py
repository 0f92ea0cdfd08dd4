"""Compile rehearsals of the fused posterior kernel for a TPU v5e.

Nothing runs here: each case compiles ``matern52_posterior`` for one chip
of a ``v5e:2x2`` topology that is described, not attached, with x64 on as
every entry point runs it.  What Mosaic refuses (a lowering it cannot
legalize, a block that overflows VMEM) fails here at no chip time.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.matern.kernel import MAX_TRAIN, matern52_posterior

D = 20          # study dimension of the chip smoke
Q = 10          # one study's restarts per evaluation round
SLOTS = 16      # fleet slot-block width (the kernel runs under vmap)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


def _compile(one_chip, n: int, vmapped: bool):
    assert jax.config.jax_enable_x64

    def arg(*shape):
        lead = (SLOTS,) if vmapped else ()
        return jax.ShapeDtypeStruct(lead + shape, jnp.float64,
                                    sharding=one_chip)

    fn = jax.vmap(matern52_posterior) if vmapped else matern52_posterior
    return jax.jit(fn).lower(arg(Q, D), arg(n, D), arg(n), arg(n, n),
                             arg(D), arg()).compile()


@pytest.mark.parametrize("vmapped", [False, True], ids=["alone", "vmap"])
@pytest.mark.parametrize("n", [224, MAX_TRAIN])
def test_posterior_compiles_for_v5e(one_chip, n, vmapped):
    compiled = _compile(one_chip, n, vmapped)
    assert "tpu_custom_call" in compiled.as_text()


def test_posterior_above_max_train_raises(one_chip):
    with pytest.raises(ValueError, match="MAX_TRAIN"):
        _compile(one_chip, MAX_TRAIN + 1, vmapped=False)
