"""Compile every Pallas kernel of the solve path for a TPU v5e chip.

The chip is described, not attached (``jax.experimental.topologies``), so
these tests run on any host that has libtpu installed.  They catch what
interpret mode cannot: block shapes the TPU tiling refuses, scalar loads
from the wrong memory space, casts Mosaic does not lower.  Shapes are a
deployment's: an 8192 x 8192 client block at rank 128.

The topology is described inside a fixture (never at import): only one
process at a time may load libtpu, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import huber_contract as hc
from repro.kernels import shrinkage as sh

N = 8192
RANK = 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no libtpu"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shapes(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return {
        "u": sds((N, RANK)), "v": sds((N, RANK)), "m": sds((N, N)),
        "m16": sds((N, N), jnp.bfloat16), "w": sds((N, N)),
        "wp": sds((N, N // 8), jnp.uint8), "lam": sds(()),
    }


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


#: kernel name -> (callable, operand names); every call compiles for the
#: chip (``interpret=False``).
KERNELS = {
    "contract_v": (hc.huber_contract_v, ("u", "v", "m", "lam")),
    "contract_u": (hc.huber_contract_u, ("u", "v", "m", "lam")),
    "contract_v_masked": (hc.huber_contract_v_masked,
                          ("u", "v", "m", "w", "lam")),
    "contract_u_masked": (hc.huber_contract_u_masked,
                          ("u", "v", "m", "w", "lam")),
    "dual": (hc.huber_dual_contract, ("u", "v", "m", "lam")),
    "dual_masked": (hc.huber_dual_contract_masked,
                    ("u", "v", "m", "w", "lam")),
    "u_diag": (hc.huber_contract_u_diag, ("u", "v", "m", "lam")),
    "u_diag_masked": (hc.huber_contract_u_diag_masked,
                      ("u", "v", "m", "w", "lam")),
    "contract_v_bf16": (hc.huber_contract_v, ("u", "v", "m16", "lam")),
    "dual_bf16": (hc.huber_dual_contract, ("u", "v", "m16", "lam")),
    "residual_shrink": (sh.residual_shrink, ("u", "v", "m", "lam")),
    "residual_shrink_masked": (sh.residual_shrink_masked,
                               ("u", "v", "m", "w", "lam")),
    "residual_shrink_psi": (sh.residual_shrink_psi, ("u", "v", "m", "lam")),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(shapes, name):
    kernel, operands = KERNELS[name]
    text = _compiled_text(
        lambda *a: kernel(*a, interpret=False),
        *(shapes[k] for k in operands),
    )
    assert "tpu_custom_call" in text


#: Serving lanes vmap the kernels over slots, each slot with its own lam.
VMAPPED = {
    "contract_v": hc.huber_contract_v,
    "u_diag_masked": hc.huber_contract_u_diag_masked,
    "residual_shrink_masked": sh.residual_shrink_masked,
}


@pytest.mark.parametrize("name", sorted(VMAPPED))
def test_vmapped_kernel_per_problem_lam_compiles(topo, name):
    one_chip = SingleDeviceSharding(topo.devices[0])
    slots, m, n, r = 4, 2048, 1024, 32

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    data = (sds((slots, m, r)), sds((slots, n, r)), sds((slots, m, n)))
    if name != "contract_v":
        data = data + (sds((slots, m, n)),)
    kernel = VMAPPED[name]
    text = _compiled_text(
        jax.vmap(lambda *a: kernel(*a, interpret=False)),
        *data, sds((slots,)),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", [hc.huber_contract_v_packed,
                                    hc.huber_contract_u_packed])
def test_packed_mask_kernels_refused_for_tpu(shapes, kernel):
    """Packed masks do not lower yet: the compiled path raises, it never
    unpacks behind the caller's back."""
    with pytest.raises(NotImplementedError, match="bit-packed masks"):
        _compiled_text(
            lambda *a: kernel(*a, interpret=False),
            *(shapes[k] for k in ("u", "v", "m", "wp", "lam")),
        )
