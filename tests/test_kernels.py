"""Pallas kernel tests: shape/dtype sweep vs pure-jnp oracles.

The gather kernel is local (single device, HLO interpreter); the remote-DMA
a2a kernels need multiple devices and run via tests/test_distributed.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, strategies as st

from repro.kernels import ops, ref


@pytest.mark.parametrize("rows,feat,dtype", [
    (32, 128, jnp.float32),
    (40, 100, jnp.float32),      # unaligned feature -> lane padding
    (64, 256, jnp.bfloat16),
    (8, 64, jnp.float32),
    (128, 512, jnp.float16),
    (48, 200, jnp.int8),         # four lanes per 32-bit word
])
def test_gather_rows_sweep(rows, feat, dtype):
    rng = np.random.default_rng(rows + feat)
    x = jnp.asarray(rng.standard_normal((rows, feat)), dtype)
    n = ((rows * 2 + 7) // 8) * 8
    idx = jnp.asarray(rng.integers(0, rows, n), jnp.int32)
    valid = jnp.asarray(rng.integers(0, 2, n), jnp.int32)
    got = ops.pack(x, idx, valid)
    want = ref.pack_ref(x, idx, valid)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=1e-6)


def test_gather_multi_dim_features():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((16, 3, 5)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 16, 24), jnp.int32)
    valid = jnp.ones(24, jnp.int32)
    got = ops.unpack(x, idx, valid)
    want = ref.unpack_ref(x, idx, valid)
    np.testing.assert_allclose(got, want)


@settings(max_examples=10)
@given(st.integers(1, 40), st.integers(1, 130), st.data())
def test_gather_rows_property(rows, feat, data):
    """Hypothesis: any index map + mask matches the oracle exactly."""
    n = data.draw(st.integers(1, 8)) * 8
    rng = np.random.default_rng(rows * 1000 + feat)
    x = jnp.asarray(rng.standard_normal((rows, feat)), jnp.float32)
    idx = jnp.asarray(
        data.draw(st.lists(st.integers(0, rows - 1), min_size=n, max_size=n)),
        jnp.int32)
    valid = jnp.asarray(
        data.draw(st.lists(st.integers(0, 1), min_size=n, max_size=n)),
        jnp.int32)
    got = ops.pack(x, idx, valid)
    want = ref.pack_ref(x, idx, valid)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_a2a_oracle_is_involution():
    """The bucket-transpose oracle applied twice is the identity."""
    rng = np.random.default_rng(1)
    p, cap, f = 4, 8, 16
    x = rng.standard_normal((p, p * cap, f)).astype(np.float32)
    once = ref.a2a_bucketed_ref(x, p, cap)
    twice = ref.a2a_bucketed_ref(once, p, cap)
    np.testing.assert_array_equal(twice, x)


@pytest.mark.parametrize("dtype,rows,d,f,n", [
    (jnp.float32, 24, 128, 128, 16),
    (jnp.bfloat16, 40, 200, 96, 12),     # D, F and N all need padding
])
def test_fused_unpack_matmul_kernel_interpret(dtype, rows, d, f, n):
    """The Pallas gather-matmul (HLO interpreter) equals its jnp form."""
    rng = np.random.default_rng(rows + d)
    e = 3
    x = jnp.asarray(rng.standard_normal((rows, d)), dtype)
    w = jnp.asarray(rng.standard_normal((e, d, f)) / np.sqrt(d), dtype)
    idx = jnp.asarray(rng.integers(0, rows, (e, n)), jnp.int32)
    valid = jnp.asarray(rng.integers(0, 2, (e, n)), jnp.int32)
    got = ops.fused_unpack_matmul(x, idx, w, valid=valid, interpret=True)
    h = ref.gather_rows_ref(x, idx.reshape(-1), valid.reshape(-1))
    want = jnp.einsum("end,edf->enf", h.reshape(e, n, d).astype(jnp.float32),
                      w.astype(jnp.float32))
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol,
                               atol=tol)
