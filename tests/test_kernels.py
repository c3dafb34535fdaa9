"""Per-kernel shape/dtype sweeps asserting exact equality vs ref.py oracles.

Pallas kernels run in interpret mode on CPU (TPU is the compile target);
interpret executes the kernel body per grid cell, so these sweeps exercise
multi-cell grids, padding/tail handling, and block-size overrides.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bitplane import unpack_bits
from repro.kernels import ops, ref

RNG = np.random.default_rng(123)


def words(*shape):
    return RNG.integers(0, 2**32, shape, dtype=np.uint32)


# ---------------------------------------------------------------------------
# fused bitwise
# ---------------------------------------------------------------------------

SHAPES = [(1, 128), (8, 128), (3, 100), (16, 384), (17, 999)]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("op", ["and", "or", "xor", "nand", "nor", "xnor",
                                "andnot"])
def test_bitwise_binary(op, shape):
    a, b = words(*shape), words(*shape)
    got = np.asarray(ops.bitwise(op, a, b, block_rows=8, block_cols=128))
    exp = np.asarray(ref.bitwise(op, a, b))
    np.testing.assert_array_equal(got, exp)


@pytest.mark.parametrize("shape", SHAPES)
def test_bitwise_not_maj3(shape):
    a, b, c = words(*shape), words(*shape), words(*shape)
    np.testing.assert_array_equal(
        np.asarray(ops.bitwise("not", a, block_rows=8, block_cols=128)),
        np.asarray(ref.bitwise("not", a)))
    np.testing.assert_array_equal(
        np.asarray(ops.bitwise("maj3", a, b, c, block_rows=8, block_cols=128)),
        np.asarray(ref.bitwise("maj3", a, b, c)))


def test_bitwise_1d():
    a, b = words(256), words(256)
    np.testing.assert_array_equal(np.asarray(ops.bitwise("xor", a, b)), a ^ b)


# ---------------------------------------------------------------------------
# majority-k (generalized TRA)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [1, 2, 3, 4, 5, 8, 9, 15, 16, 33])
def test_majority_k(k):
    planes = words(k, 8, 128)
    got = np.asarray(ops.majority(jnp.asarray(planes)))
    exp = np.asarray(ref.majority_k(jnp.asarray(planes)))
    np.testing.assert_array_equal(got, exp)


def test_majority3_equals_tra():
    """MAJ3 kernel == the engine's triple-row activation semantics."""
    from repro.core import compiler, engine

    a, b, c = words(64), words(64), words(64)
    prog = compiler.op_program("maj3", ["D0", "D1", "D2"], "D3")
    tra = engine.execute(prog, {"D0": a, "D1": b, "D2": c}, outputs=["D3"])["D3"]
    ker = ops.majority(jnp.stack([a, b, c]))
    np.testing.assert_array_equal(np.asarray(ker), np.asarray(tra))


@pytest.mark.parametrize("k,thresh", [(8, 1), (8, 8), (5, 2), (16, 11)])
def test_majority_custom_threshold(k, thresh):
    planes = words(k, 8, 128)
    got = np.asarray(ops.majority(jnp.asarray(planes), threshold=thresh))
    exp = np.asarray(ref.majority_k(jnp.asarray(planes), threshold=thresh))
    np.testing.assert_array_equal(got, exp)


# ---------------------------------------------------------------------------
# popcount
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(8, 128), (1, 1), (5, 300), (32, 1000)])
def test_popcount(shape):
    x = words(*shape)
    got = int(ops.popcount(x, block_rows=8, block_cols=128))
    exp = int(np.unpackbits(x.view(np.uint8)).sum())
    assert got == exp


def test_popcount_extremes():
    assert int(ops.popcount(np.zeros((8, 128), np.uint32))) == 0
    assert int(ops.popcount(np.full((8, 128), 0xFFFFFFFF, np.uint32))) == 8 * 128 * 32


# ---------------------------------------------------------------------------
# bit transpose (BitWeaving-V layout)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_bits", [1, 4, 7, 12, 16, 32])
@pytest.mark.parametrize("n_vals", [32, 320, 32 * 200])
def test_bit_transpose(n_bits, n_vals):
    vals = RNG.integers(0, 2**n_bits, n_vals, dtype=np.uint64).astype(np.uint32)
    got = np.asarray(ops.bit_transpose(jnp.asarray(vals), n_bits,
                                       block_groups=128))
    exp = np.asarray(ref.bit_transpose(jnp.asarray(vals), n_bits))
    np.testing.assert_array_equal(got, exp)
    # roundtrip
    back = np.asarray(ops.bit_untranspose(jnp.asarray(got), n_bits,
                                          block_groups=128))
    np.testing.assert_array_equal(back, vals)


# ---------------------------------------------------------------------------
# bitweaving predicate scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_bits", [4, 8, 12, 16])
def test_bitweaving_scan_sweep(n_bits):
    n = 32 * 96
    vals = RNG.integers(0, 2**n_bits, n, dtype=np.uint64).astype(np.uint32)
    planes = ref.bit_transpose(jnp.asarray(vals), n_bits)
    lo = int(RNG.integers(0, 2**n_bits // 2))
    hi = int(RNG.integers(lo, 2**n_bits))
    got = ops.bitweaving_scan(planes, lo, hi, n_bits, block_cols=128)
    bits = np.asarray(unpack_bits(got, n))
    np.testing.assert_array_equal(bits, (vals >= lo) & (vals <= hi))
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ref.bitweaving_scan(planes, lo, hi, n_bits)))


def test_bitweaving_scan_edge_constants():
    n_bits, n = 8, 32 * 8
    vals = RNG.integers(0, 256, n, dtype=np.uint64).astype(np.uint32)
    planes = ref.bit_transpose(jnp.asarray(vals), n_bits)
    for lo, hi in [(0, 255), (0, 0), (255, 255), (7, 7)]:
        got = np.asarray(unpack_bits(
            ops.bitweaving_scan(planes, lo, hi, n_bits), n))
        np.testing.assert_array_equal(got, (vals >= lo) & (vals <= hi))


# ---------------------------------------------------------------------------
# sign pack / unpack
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(1, 32), (8, 320), (5, 32 * 50)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_pack_signs(shape, dtype):
    x = RNG.standard_normal(shape).astype(dtype)
    got = np.asarray(ops.pack_signs(jnp.asarray(x), block_rows=8,
                                    block_words=128))
    exp = np.asarray(ref.pack_signs(jnp.asarray(x)))
    np.testing.assert_array_equal(got, exp)


def test_pack_unpack_roundtrip_signs():
    x = RNG.standard_normal((4, 320)).astype(np.float32)
    x[x == 0] = 1.0
    w = ops.pack_signs(jnp.asarray(x))
    u = np.asarray(ops.unpack_signs(w))
    np.testing.assert_array_equal(u, np.where(x < 0, -1.0, 1.0).astype(np.float32))


def test_pack_signs_negative_zero():
    """IEEE -0.0 has the sign bit set; bitcast path must agree with ref."""
    x = np.array([[0.0, -0.0, 1.0, -1.0] * 8], np.float32)
    got = np.asarray(ops.pack_signs(jnp.asarray(x)))
    exp = np.asarray(ref.pack_signs(jnp.asarray(x)))
    np.testing.assert_array_equal(got, exp)


# ---------------------------------------------------------------------------
# device gating: interpret mode and the peak table
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False),
                                               ("gpu", None)])
def test_use_interpret_by_backend(monkeypatch, backend, interpret):
    """The kernels compile on a TPU, interpret on the CPU, and refuse any
    other backend rather than silently interpreting there."""
    import jax

    from repro.kernels.common import use_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="gpu"):
            use_interpret.__wrapped__()
    else:
        assert use_interpret.__wrapped__() is interpret


def test_peaks_table_keyed_by_device_kind():
    from repro import hw

    v5e = hw.peaks("TPU v5 lite")
    assert (v5e.flops_bf16, v5e.hbm_bw, v5e.hbm_bytes) == (197e12, 819e9,
                                                           16 * 10**9)
    assert "TPU v5e" in v5e.source
    with pytest.raises(KeyError, match="no peak entry"):
        hw.peaks("cpu")
