"""The APSP Pallas kernels compile for a TPU v5e at N=1024, and the ELL
round at the N=16384 frontier it exists for.

No chip is needed: the TPU compiler compiles for a described, unattached
v5e, and refuses what the chip's compiler would refuse (lowering gaps,
misaligned slices, VMEM overruns).  Interpret-mode tests cannot see any
of that.  Each test asserts the compiled program holds the Mosaic kernel
(``tpu_custom_call``), so a kernel that silently became plain XLA fails.

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and test workers each import
every test file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ell as kell
from repro.kernels import fw as kfw
from repro.kernels import minplus

N = 1024
N_FRONTIER = 16384   # scale_bench's ell-bf frontier
D_MAX = 16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip cannot read back what the persistent cache stores
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text(), \
        "the compiled program holds no Mosaic kernel"


def test_minplus_matmul_compiles_for_v5e(one_chip):
    a = _spec(one_chip, (N, N))
    _assert_kernel(minplus.minplus_matmul_pallas.lower(
        a, a, interpret=False).compile())


def test_blocked_fw_compiles_for_v5e(one_chip):
    w = _spec(one_chip, (N, N))
    _assert_kernel(kfw.fw_apsp_pallas.lower(
        w, t=128, interpret=False).compile())


def _compile_ell(sharding, n):
    """The closure the ``ell-bf`` backend runs on the chip, whose while
    loop holds the Pallas round."""
    idx = _spec(sharding, (n, D_MAX), jnp.int32)
    wgt = _spec(sharding, (n, D_MAX))
    return kell.ell_bf_apsp.lower(idx, wgt, max_rounds=8, use_pallas=True,
                                  interpret=False).compile()


def test_ell_round_compiles_for_v5e(one_chip):
    _assert_kernel(_compile_ell(one_chip, N))


def test_ell_round_fits_smem_at_frontier(one_chip):
    """Each grid step blocks only its target tile's rows of the tables
    into SMEM (1 MiB on v5e); whole tables prefetched there would need
    2 MiB at this N."""
    _assert_kernel(_compile_ell(one_chip, N_FRONTIER))


def test_ell_closure_compiles_vmapped_for_v5e(one_chip):
    """The solvers run the closure per lane under vmap."""
    idx = _spec(one_chip, (4, N, D_MAX), jnp.int32)
    wgt = _spec(one_chip, (4, N, D_MAX))
    fn = jax.jit(jax.vmap(functools.partial(
        kell.ell_bf_apsp_impl, max_rounds=8, use_pallas=True,
        interpret=False)))
    _assert_kernel(fn.lower(idx, wgt).compile())
