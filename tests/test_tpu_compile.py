"""Compile the served path and every Pallas kernel for a described TPU v5e.

Nothing runs: the TPU compiler is handed a chip that is described, not
attached, and refuses what the chip would refuse (block shapes off the
(8, 128) tiling, too much VMEM, a program that does not fit HBM).  The
topology is described inside a fixture so that every pytest-xdist worker
collects the same tests and only the worker given this file loads libtpu.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.configs.base import ShapeConfig
from repro.kernels import (dispatch_score_update, dispatch_scores,
                           flash_attention, moe_gmm, rglru_scan, wkv6)
from repro.models import (cache_init, make_decode_step, make_prefill_step,
                          param_specs)

F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_described_chip_is_v5e(one_chip):
    (dev,) = one_chip.device_set
    assert dev.platform == "tpu" and dev.device_kind == "TPU v5 lite"


# ------------------------------------------------- served path, full width
CFG = get_arch("internlm2-1.8b")


def test_serve_decode_step_compiles_full_width(one_chip):
    """The server's jitted decode step at cache cap 2048 fits one chip."""
    params = _on(one_chip, param_specs(CFG))
    caches = _on(one_chip, jax.eval_shape(lambda: cache_init(CFG, 1, 2048)))
    batch = {"token": _sds((1,), I32, one_chip),
             "pos": _sds((), I32, one_chip), "caches": caches}
    compiled = jax.jit(make_decode_step(CFG)).lower(params, batch).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 16e9


def test_serve_prefill_step_compiles_full_width(one_chip):
    """The server's jitted prefill step over a 512-token prompt."""
    params = _on(one_chip, param_specs(CFG))
    shape = ShapeConfig("serve", "prefill", 2048, 1)
    batch = {"tokens": _sds((1, 512), I32, one_chip)}
    compiled = jax.jit(make_prefill_step(CFG, shape)).lower(
        params, batch).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


# ------------------------------------------------ Pallas kernels, real widths
def _kernel_cases(s):
    """(name, jitted fn, args, static kwargs) at real widths."""
    return [
        # dispatch window 3200 x 64 executors x 4096 objects
        ("dispatch_scores", dispatch_scores,
         (_sds((3200, 4096), F32, s), _sds((64, 4096), F32, s)), {}),
        ("dispatch_score_update", dispatch_score_update,
         (_sds((3200, 64), F32, s), _sds((3200, 512), F32, s),
          _sds((512, 64), F32, s)), {}),
        # internlm2-1.8b attention: 16 query heads over 8 KV heads, D=128
        ("flash_attention", flash_attention,
         (_sds((1, 2048, 16, 128), BF16, s), _sds((1, 2048, 8, 128), BF16, s),
          _sds((1, 2048, 8, 128), BF16, s)), {}),
        # olmoe-1b-7b experts: 64 x (2048 -> 1024)
        ("moe_gmm", moe_gmm,
         (_sds((64, 256, 2048), BF16, s), _sds((64, 2048, 1024), BF16, s)),
         {}),
        # recurrentgemma-9b RG-LRU width 4096
        ("rglru_scan", rglru_scan,
         (_sds((1, 2048, 4096), F32, s), _sds((1, 2048, 4096), F32, s)), {}),
        # rwkv6-3b: 40 heads of 64
        ("wkv6", wkv6,
         tuple(_sds((1, 2048, 40, 64), F32, s) for _ in range(4))
         + (_sds((40, 64), F32, s),), {}),
    ]


KERNELS = ["dispatch_scores", "dispatch_score_update", "flash_attention",
           "moe_gmm", "rglru_scan", "wkv6"]


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, name):
    cases = {c[0]: c for c in _kernel_cases(one_chip)}
    _, fn, args, kw = cases[name]
    compiled = fn.lower(*args, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
