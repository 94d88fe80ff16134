"""Per-kernel shape/dtype sweeps against the pure-jnp oracles."""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # hypothesis or skip-shim

from repro.kernels import (
    ccu_reduce, flash_attention, moe_dispatch, ops, ref, rwkv6_scan, ssd_scan,
)

KEY = jax.random.PRNGKey(42)


def rand(key, shape, dtype=jnp.float32, scale=0.5):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


class TestFlashAttention:
    @pytest.mark.parametrize("B,K,G,S,D", [
        (1, 1, 1, 128, 64),
        (2, 2, 3, 256, 64),
        (1, 4, 2, 256, 128),
        (2, 1, 8, 128, 32),     # MQA
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_shapes_dtypes(self, B, K, G, S, D, dtype):
        ks = jax.random.split(KEY, 3)
        q = rand(ks[0], (B, K, G, S, D), dtype)
        k = rand(ks[1], (B, K, S, D), dtype)
        v = rand(ks[2], (B, K, S, D), dtype)
        o = ops.flash_attention_bkgsd(q, k, v, causal=True)
        r = ref.attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(o, np.float32), np.asarray(r, np.float32),
            atol=TOL[dtype], rtol=TOL[dtype],
        )

    @pytest.mark.parametrize("kwargs", [
        dict(causal=True, window=64),
        dict(causal=True, prefix_len=48),
        dict(causal=False),
        dict(causal=True, window=32, prefix_len=16),
    ])
    def test_masks(self, kwargs):
        ks = jax.random.split(KEY, 3)
        q = rand(ks[0], (2, 2, 2, 256, 64))
        k = rand(ks[1], (2, 2, 256, 64))
        v = rand(ks[2], (2, 2, 256, 64))
        o = ops.flash_attention_bkgsd(q, k, v, **kwargs)
        r = ref.attention_ref(q, k, v, **kwargs)
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5)

    def test_block_size_invariance(self):
        ks = jax.random.split(KEY, 3)
        q = rand(ks[0], (1, 2, 2, 256, 64))
        k = rand(ks[1], (1, 2, 256, 64))
        v = rand(ks[2], (1, 2, 256, 64))
        o1 = ops.flash_attention_bkgsd(q, k, v, block_q=64, block_k=64)
        o2 = ops.flash_attention_bkgsd(q, k, v, block_q=128, block_k=128)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-6)

    def test_model_layout_wrapper(self):
        ks = jax.random.split(KEY, 3)
        B, S, N, K, D = 2, 128, 8, 2, 64
        q = rand(ks[0], (B, S, N, D))
        k = rand(ks[1], (B, S, K, D))
        v = rand(ks[2], (B, S, K, D))
        o = ops.flash_attention_bsnd(q, k, v, causal=True)
        from repro.models.layers import sdpa, _mask_bias

        qg = q.reshape(B, S, K, N // K, D)
        bias = _mask_bias(jnp.arange(S), jnp.arange(S), True, None)
        r = sdpa(qg, k, v, bias).reshape(B, S, N, D)
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5)


class TestSSDScan:
    @pytest.mark.parametrize("B,S,H,P,N,chunk", [
        (1, 128, 2, 16, 16, 64),
        (2, 256, 4, 32, 16, 128),
        (1, 256, 1, 64, 64, 32),
    ])
    def test_matches_recurrence(self, B, S, H, P, N, chunk):
        ks = jax.random.split(KEY, 4)
        xh = rand(ks[0], (B, S, H, P))
        ll = -jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
        Bm = rand(ks[2], (B, S, N))
        Cm = rand(ks[3], (B, S, N))
        y, h = ops.ssd_scan(xh, ll, Bm, Cm, chunk=chunk)
        yr, hr = ref.ssd_scan_ref(xh, ll, Bm, Cm)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=5e-5)
        np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=5e-5)

    def test_strong_decay_is_stable(self):
        """the failure mode that NaN'd the factored form"""
        ks = jax.random.split(KEY, 4)
        B, S, H, P, N = 1, 256, 2, 16, 16
        xh = rand(ks[0], (B, S, H, P))
        ll = jnp.full((B, S, H), -13.0)      # near-total forgetting
        Bm = rand(ks[2], (B, S, N))
        Cm = rand(ks[3], (B, S, N))
        y, h = ops.ssd_scan(xh, ll, Bm, Cm, chunk=128)
        assert np.isfinite(np.asarray(y)).all()


class TestRWKV6Scan:
    @pytest.mark.parametrize("B,S,H,N,chunk", [
        (1, 64, 1, 16, 32),
        (2, 128, 2, 32, 32),
        (1, 256, 4, 64, 128),
    ])
    def test_matches_recurrence(self, B, S, H, N, chunk):
        ks = jax.random.split(KEY, 5)
        r = rand(ks[0], (B, S, H, N))
        k = rand(ks[1], (B, S, H, N))
        v = rand(ks[2], (B, S, H, N))
        w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, N))) * 0.98 + 0.01
        u = rand(ks[4], (H, N), scale=0.3)
        y, s = ops.rwkv6_scan(r, k, v, w, u, chunk=chunk, tile=16)
        yr, sr = ref.rwkv6_scan_ref(r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=5e-5)
        np.testing.assert_allclose(np.asarray(s), np.asarray(sr), atol=5e-5)

    def test_extreme_decay_stable(self):
        ks = jax.random.split(KEY, 5)
        B, S, H, N = 1, 128, 1, 16
        r = rand(ks[0], (B, S, H, N))
        k = rand(ks[1], (B, S, H, N))
        v = rand(ks[2], (B, S, H, N))
        w = jnp.full((B, S, H, N), 1e-6)     # decays that overflow exp(-cum)
        u = rand(ks[4], (H, N))
        y, s = ops.rwkv6_scan(r, k, v, w, u, chunk=64)
        assert np.isfinite(np.asarray(y)).all()
        yr, sr = ref.rwkv6_scan_ref(r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=5e-4)


class TestMoEDispatch:
    @given(st.integers(1, 4), st.integers(16, 64))
    @settings(max_examples=8, deadline=None)
    def test_property_random_routing(self, e_pow, c):
        E = 2 ** e_pow
        T, D = 128, 32
        rng = np.random.default_rng(E * 100 + c)
        idx = rng.integers(0, E, T)
        disp = np.zeros((T, E, c), np.float32)
        cnt = np.zeros(E, int)
        for t in range(T):
            e = idx[t]
            if cnt[e] < c:
                disp[t, e, cnt[e]] = 1.0
                cnt[e] += 1
        disp = jnp.asarray(disp)
        x = rand(KEY, (T, D))
        out = ops.moe_dispatch(disp, x, block_t=64)
        expect = jnp.einsum("tec,td->ecd", disp, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-5)


class TestCCUReduce:
    @pytest.mark.parametrize("P,N,block", [(2, 512, 512), (8, 2048, 512), (16, 1024, 256)])
    def test_matches_sum(self, P, N, block):
        bufs = rand(KEY, (P, N))
        out = ops.ccu_reduce(bufs, block_n=block)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref.ccu_reduce_ref(bufs)), atol=1e-5
        )

    def test_int8_dequant_ingestion(self):
        """compressed-gradient ingestion: int8 peers + per-peer scales"""
        rng = np.random.default_rng(0)
        P, N = 4, 1024
        q = jnp.asarray(rng.integers(-127, 128, (P, N), dtype=np.int8))
        scales = jnp.asarray(rng.uniform(0.5, 2.0, P).astype(np.float32))
        out = ops.ccu_reduce(q, scales, block_n=512)
        expect = (np.asarray(q, np.float32) * np.asarray(scales)[:, None]).sum(0)
        np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5, atol=1e-3)

    def test_deterministic_order(self):
        """same peers, same order => bitwise identical (CCU determinism)"""
        bufs = rand(KEY, (8, 1024))
        o1 = np.asarray(ops.ccu_reduce(bufs))
        o2 = np.asarray(ops.ccu_reduce(bufs))
        assert (o1 == o2).all()


@pytest.mark.parametrize("fn", [
    flash_attention.flash_attention,
    ssd_scan.ssd_scan,
    rwkv6_scan.rwkv6_scan,
    moe_dispatch.moe_dispatch,
    moe_dispatch.moe_gather_matmul,
    ccu_reduce.ccu_reduce,
])
def test_interpret_mode_has_no_default(fn):
    """ops.py picks interpret mode by backend; a kernel never defaults to it."""
    default = inspect.signature(fn).parameters["interpret"].default
    assert default is inspect.Parameter.empty
