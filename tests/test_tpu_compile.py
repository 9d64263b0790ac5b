"""Every kernel on the query path, compiled for a TPU v5e at serving
shapes — without a chip. Interpret mode never checks tiling, lowering
or VMEM; the TPU compiler installed with JAX does, for a chip that is
described (``jax.experimental.topologies``) rather than attached.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports every test file. Where it cannot be described the
fixture skips this file's tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import dispatch
from repro.kernels.label_intersect.kernel import (
    label_intersect_kernel, label_intersect_packed_kernel)
from repro.kernels.minplus_matmul.kernel import minplus_matmul_kernel
from repro.kernels.spmv_relax.kernel import (fused_relax_kernel,
                                             fused_vmem_bytes)

N_SENTINEL = 1_000_000      # the 10^6-vertex ER index (docs/CONSTRUCTION.md)
CORE_V = 187_904            # its core, 187,852 vertices + sentinel, padded
Q_SERVE = 256               # the largest serving bucket


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **static):
    return fn.lower(*args, **static).compile().as_text()


@pytest.mark.parametrize("l", [128, 512])
def test_label_intersect_compiles(one_chip, l):
    row_i = _spec(one_chip, (Q_SERVE, l), jnp.int32)
    row_d = _spec(one_chip, (Q_SERVE, l), jnp.float32)
    hlo = _compile(label_intersect_kernel, row_i, row_d, row_i, row_d,
                   n_sentinel=N_SENTINEL, bq=8)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("l,d_dtype", [(128, jnp.int32), (512, jnp.int32),
                                       (128, jnp.float32)])
def test_label_intersect_packed_compiles(one_chip, l, d_dtype):
    delta = _spec(one_chip, (Q_SERVE, l), jnp.int16)
    base = _spec(one_chip, (Q_SERVE, 1), jnp.int32)
    d = _spec(one_chip, (Q_SERVE, l), d_dtype)
    hlo = _compile(label_intersect_packed_kernel, delta, base, d,
                   delta, base, d, n_sentinel=N_SENTINEL, bq=16)
    assert "tpu_custom_call" in hlo


def test_minplus_compiles_as_dense_route_calls_it(one_chip):
    m = _spec(one_chip, (2048, 2048), jnp.float32)
    hlo = _compile(minplus_matmul_kernel, m, m, bm=8)
    assert "tpu_custom_call" in hlo


def test_ell_xla_round_compiles_at_the_million_vertex_core(one_chip):
    """The stage-2 route every large core takes is an XLA program (no
    Mosaic gather spans more than one vreg): no kernel inside."""
    q = 64
    seed = _spec(one_chip, (q, CORE_V - 1), jnp.float32)
    ids = _spec(one_chip, (CORE_V, 16), jnp.int32)
    w = _spec(one_chip, (CORE_V, 16), jnp.float32)
    mu = _spec(one_chip, (q,), jnp.float32)
    hlo = _compile(dispatch._core_relax_ell, seed, seed, ids, w, mu,
                   n_core=CORE_V - 2, max_rounds=CORE_V - 2)
    assert "tpu_custom_call" not in hlo


def _widest_fused_ell(vp, bq=8):
    width = 16
    while dispatch.fused_fits(vp, width + 16, bq):
        width += 16
    return width


def test_fused_compiles_at_the_largest_shape_its_budget_admits(one_chip):
    vp = dispatch.FUSED_MAX_V
    width = _widest_fused_ell(vp)
    assert dispatch.fused_fits(vp, width, 8)
    rows = _spec(one_chip, (2 * Q_SERVE, vp), jnp.float32)
    ids = _spec(one_chip, (width, vp), jnp.int32)
    w = _spec(one_chip, (width, vp), jnp.float32)
    hlo = _compile(fused_relax_kernel, rows, ids, w, max_rounds=vp, bq=8)
    assert "tpu_custom_call" in hlo


def test_fused_vmem_model_is_what_the_compiler_allocates(one_chip):
    """One slot group past the budget, the compiler itself refuses the
    kernel: ``fused_vmem_bytes`` and ``FUSED_VMEM_BUDGET`` agree with
    the scoped-VMEM allocation the compiler makes."""
    vp = dispatch.FUSED_MAX_V
    width = _widest_fused_ell(vp) + 16
    assert fused_vmem_bytes(vp, width) > dispatch.FUSED_VMEM_BUDGET
    rows = _spec(one_chip, (2 * Q_SERVE, vp), jnp.float32)
    ids = _spec(one_chip, (width, vp), jnp.int32)
    w = _spec(one_chip, (width, vp), jnp.float32)
    with pytest.raises(Exception, match="vmem"):
        _compile(fused_relax_kernel, rows, ids, w, max_rounds=vp, bq=8)
