"""The served path's kernels compile for a TPU v5e at deployment width.

Everywhere else the tests run on the CPU, where the Pallas kernels are
interpreted, so a kernel that the chip's compiler refuses passes them all.
These tests compile the real kernels for a v5e chip described by
`jax.experimental.topologies` (no chip attached) at one §8.1 bitmap's
width: 16M users, 524,288 words a row. A block layout that Mosaic refuses
fails here. Nothing runs, so nothing here checks results.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import lowering
from repro.kernels import (arith, bittranspose, bitweaving, bitwise,
                           majority, popcount, vm)

WORDS = 524_288            # 1 << 24 bits
N_ROWS = 40
N_CMDS = 64
BATCH = 4
OUT_IDX = (9, 10)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled(monkeypatch):
    """Steer the kernels off interpret mode: compile them for the chip."""
    for mod in (vm, arith, bittranspose, bitweaving, bitwise, majority,
                popcount):
        monkeypatch.setattr(mod, "use_interpret", lambda: False)


def _sds(shape, sharding, dtype=jnp.uint32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(lowered) -> bool:
    return "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("variant", ["materialize", "popcount_shared_mask",
                                     "popcount_batch_mask", "errors"])
def test_megakernel_compiles(variant, one_chip, compiled):
    """`_vm_call` in every variant the served path reaches; the per-batch
    mask is the one the sharded and banked count paths pass."""
    table = _sds((N_CMDS, 5), one_chip, jnp.int32)
    plane = _sds((BATCH, N_ROWS, WORDS), one_chip)
    errors = mask = None
    reduce = None
    if variant == "errors":
        errors = _sds((BATCH, 4 * N_CMDS, WORDS), one_chip)
    elif variant != "materialize":
        reduce = "popcount"
        mb = 1 if variant == "popcount_shared_mask" else BATCH
        mask = _sds((mb, WORDS), one_chip)
    lowered = vm._vm_call.lower(table, plane, errors, mask, out_idx=OUT_IDX,
                                block_cols=vm.DEFAULT_BLOCK_COLS,
                                reduce=reduce)
    assert _has_kernel(lowered)


@pytest.mark.parametrize("backend", ["scan", "pallas"])
@pytest.mark.parametrize("reduce", [None, "popcount"])
def test_dispatch_compiles(backend, reduce, one_chip, compiled):
    """The whole jitted plan-group dispatch (`lowering._dispatch`): plane
    build, VM run and output extraction or fused count."""
    n_vals = 16
    vals = tuple(_sds((BATCH, WORDS), one_chip) for _ in range(n_vals))
    mask = _sds((WORDS,), one_chip) if reduce else None
    lowered = lowering._dispatch.lower(
        _sds((N_CMDS, 5), one_chip, jnp.int32), vals, (), None, mask,
        n_rows=N_ROWS, out_runs=((OUT_IDX[0], OUT_IDX[-1] + 1),),
        row_words=WORDS, batch=(BATCH,), backend=backend, reduce=reduce)
    assert _has_kernel(lowered) == (backend == "pallas")


@pytest.mark.parametrize("kernel", ["bitwise", "bitwise_banked", "popcount",
                                    "bitweaving", "add", "lt", "majority",
                                    "transpose", "untranspose"])
def test_per_op_kernel_compiles(kernel, one_chip, compiled):
    """The per-operation kernels behind `repro.ops`, one call each; the
    transpose encodes every registered column (`register_column`)."""
    def s(*shape):
        return _sds(shape, one_chip)

    lowered = {
        "bitwise": lambda: bitwise.bitwise_kernel.lower(
            "and", s(8, WORDS), s(8, WORDS)),
        "bitwise_banked": lambda: bitwise.banked_bitwise_kernel.lower(
            "xor", s(8, 8, WORDS // 8), s(8, 8, WORDS // 8)),
        "popcount": lambda: popcount.popcount_kernel.lower(s(8, WORDS)),
        "bitweaving": lambda: bitweaving.bitweaving_scan_kernel.lower(
            s(8, WORDS), 3, 100, 8),
        "add": lambda: arith.bitserial_add_kernel.lower(
            s(8, 8, WORDS), s(8, 8, WORDS)),
        "lt": lambda: arith.bitserial_lt_kernel.lower(
            s(8, 8, WORDS), s(8, 8, WORDS)),
        "majority": lambda: majority.majority_kernel.lower(
            s(3, 8, WORDS), None),
        "transpose": lambda: bittranspose.bit_transpose_kernel.lower(
            s(32 * WORDS)),
        "untranspose": lambda: bittranspose.bit_untranspose_kernel.lower(
            s(32, WORDS)),
    }[kernel]()
    assert _has_kernel(lowered)
