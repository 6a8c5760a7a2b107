"""Per-kernel allclose sweeps: shapes × dtypes against the pure-jnp oracles.

All Pallas kernels run in interpret mode on CPU (the kernel body executes in
Python) — exactness vs TPU differs only in fp accumulation order.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.grib_pack import grib_pack, grib_unpack
from repro.kernels.grib_pack.ref import field_stats, pack_ref
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_sequential_ref
from repro.models.ssm import ssd_chunked


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=2e-4, rtol=2e-4)


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "b,sq,sk,kh,g,d",
        [
            (1, 128, 128, 1, 1, 64),     # MHA single head
            (2, 256, 256, 2, 3, 64),     # GQA groups=3
            (1, 128, 384, 2, 2, 128),    # kv longer than q (cross-ish)
            (2, 64, 64, 4, 1, 32),       # small blocks force padding path
        ],
    )
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_ref(self, b, sq, sk, kh, g, d, causal, dtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (b, sq, kh, g, d), jnp.float32).astype(dtype)
        k = jax.random.normal(ks[1], (b, sk, kh, d), jnp.float32).astype(dtype)
        v = jax.random.normal(ks[2], (b, sk, kh, d), jnp.float32).astype(dtype)
        out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64, interpret=True)
        ref = attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype)
        )

    def test_q_offset_decode_window(self):
        """q_offset simulates continuing a causal stream mid-sequence."""
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        b, kh, g, d = 1, 1, 1, 64
        sq, sk, off = 64, 192, 128
        q = jax.random.normal(ks[0], (b, sq, kh, g, d))
        k = jax.random.normal(ks[1], (b, sk, kh, d))
        v = jax.random.normal(ks[2], (b, sk, kh, d))
        out = flash_attention(q, k, v, causal=True, q_offset=off, block_q=64, block_k=64, interpret=True)
        ref = attention_ref(q, k, v, causal=True, q_offset=off)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4)

    def test_block_shape_independence(self):
        """Different BlockSpec tilings must give identical results."""
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (1, 256, 2, 2, 64))
        k = jax.random.normal(ks[1], (1, 256, 2, 64))
        v = jax.random.normal(ks[2], (1, 256, 2, 64))
        outs = [
            flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True)
            for bq, bk in [(64, 64), (128, 128), (128, 64), (256, 256)]
        ]
        for o in outs[1:]:
            np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o), atol=1e-5, rtol=1e-5)


class TestSSDScan:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "b,s,h,p,n,chunk",
        [
            (1, 64, 1, 8, 4, 16),
            (2, 128, 3, 16, 8, 32),
            (1, 256, 2, 64, 16, 64),    # wider head_dim
            (2, 96, 2, 16, 8, 32),      # s not a power of two (96 = 3*32)
        ],
    )
    def test_kernel_and_chunked_match_sequential(self, b, s, h, p, n, chunk, dtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32).astype(dtype)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))).astype(jnp.float32)
        A = -jnp.exp(jax.random.normal(ks[2], (h,)))
        B_ = jax.random.normal(ks[3], (b, s, n), jnp.float32).astype(dtype)
        C_ = jax.random.normal(ks[4], (b, s, n), jnp.float32).astype(dtype)
        D_ = jnp.ones((h,))
        ref = ssd_sequential_ref(x, dt, A, B_, C_, D_)
        chk = ssd_chunked(x, dt, A, B_, C_, D_, chunk=chunk)
        ker = ssd_scan(x, dt, A, B_, C_, D_, chunk=chunk, interpret=True)
        t = dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else dict(atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(np.asarray(chk, np.float32), np.asarray(ref, np.float32), **t)
        np.testing.assert_allclose(np.asarray(ker, np.float32), np.asarray(ref, np.float32), **t)

    def test_state_carries_across_chunks(self):
        """A single long chunk vs many small chunks must agree (state carry)."""
        ks = jax.random.split(jax.random.PRNGKey(7), 5)
        b, s, h, p, n = 1, 128, 2, 8, 4
        x = jax.random.normal(ks[0], (b, s, h, p))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
        A = -jnp.exp(jax.random.normal(ks[2], (h,)))
        B_ = jax.random.normal(ks[3], (b, s, n))
        C_ = jax.random.normal(ks[4], (b, s, n))
        D_ = jnp.zeros((h,))
        one = ssd_scan(x, dt, A, B_, C_, D_, chunk=128, interpret=True)
        many = ssd_scan(x, dt, A, B_, C_, D_, chunk=16, interpret=True)
        np.testing.assert_allclose(np.asarray(one), np.asarray(many), atol=1e-4, rtol=1e-4)


class TestGribPack:
    @pytest.mark.parametrize("shape", [(1, 32, 128), (4, 64, 128), (2, 256, 256)])
    @pytest.mark.parametrize("nbits", [8, 16])
    def test_roundtrip_error_within_quantum(self, shape, nbits):
        x = jax.random.normal(jax.random.PRNGKey(0), shape) * 40 + 250.0
        codes, ref, scale = grib_pack(x, nbits=nbits, interpret=True)
        y = grib_unpack(codes, ref, scale, interpret=True)
        quantum = (x.max(axis=(1, 2)) - x.min(axis=(1, 2))) / ((1 << nbits) - 1)
        err = jnp.abs(y - x).max(axis=(1, 2))
        assert np.all(np.asarray(err) <= np.asarray(quantum) * 1.01)

    def test_codes_match_ref(self):
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 128)) * 10
        codes, _, _ = grib_pack(x, interpret=True)
        lo, scale, inv = field_stats(x)
        expected = pack_ref(x, lo, inv)
        # rounding boundaries can flip ±1 code
        assert np.abs(np.asarray(codes) - np.asarray(expected)).max() <= 1

    @pytest.mark.parametrize("nbits", [8, 16, 24])
    def test_codes_narrowed_to_their_container_on_the_device(self, nbits):
        from repro.kernels.grib_pack import payload_dtype

        x = jax.random.normal(jax.random.PRNGKey(5), (3, 32, 128)) * 40 + 250.0
        narrow, ref, scale = grib_pack(x, nbits=nbits, interpret=True)
        lo, _, inv = field_stats(x, nbits)
        wide = np.asarray(pack_ref(x, lo, inv, nbits))
        assert narrow.dtype == payload_dtype(nbits) and wide.dtype == np.int32
        # rounding boundaries can flip one code
        assert np.abs(np.asarray(narrow).astype(np.int64) - wide).max() <= 1
        # unpack widens a container on the device, and stacks fields handed
        # over one by one: the same floats every way
        wide = np.asarray(narrow).astype(np.int32)
        want = np.asarray(grib_unpack(wide, ref, scale, interpret=True))
        fields = tuple(np.asarray(narrow))
        for codes in (narrow, fields):
            np.testing.assert_array_equal(
                np.asarray(grib_unpack(codes, ref, scale, interpret=True)), want)

    def test_constant_field(self):
        x = jnp.full((1, 32, 128), 5.0)
        codes, ref, scale = grib_pack(x, interpret=True)
        y = grib_unpack(codes, ref, scale, interpret=True)
        np.testing.assert_allclose(np.asarray(y), 5.0, atol=1e-5)
