"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode).

Shapes and dtypes swept per kernel; hypothesis drives randomized shapes for
streaming_stats (the cheapest kernel) — for the heavier kernels fixed
parameterized sweeps keep CI time sane on one CPU core.
"""

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssm_scan.ops import ssd_scan
from repro.kernels.streaming_stats.ops import KernelMeanProgram, streaming_stats
from repro.kernels.streaming_stats.ref import streaming_stats_ref

rng = np.random.default_rng(1234)


class TestStreamingStats:
    @pytest.mark.parametrize("R,shape", [
        (1, (8,)), (16, (64,)), (256, (512,)), (300, (12, 11)),
        (64, (32, 32, 4)),
    ])
    @pytest.mark.parametrize("dtype", [np.float32, np.float16])
    def test_matches_ref(self, R, shape, dtype):
        x = rng.normal(size=(R,) + shape).astype(dtype)
        m = rng.random(R) > 0.25
        s, sq, c = streaming_stats(jnp.asarray(x), jnp.asarray(m),
                                   interpret=True)
        rs, rsq, rc = streaming_stats_ref(
            jnp.asarray(x.reshape(R, -1)), jnp.asarray(m))
        tol = 1e-5 if dtype == np.float32 else 5e-3
        np.testing.assert_allclose(np.asarray(s).reshape(-1), rs,
                                   rtol=tol, atol=tol)
        np.testing.assert_allclose(np.asarray(sq).reshape(-1), rsq,
                                   rtol=tol, atol=tol)
        assert float(c) == m.sum()

    @given(
        R=st.integers(1, 200),
        F=st.integers(1, 300),
        seed=st.integers(0, 2**31 - 1),
    )
    @settings(max_examples=20, deadline=None)
    def test_property_random_shapes(self, R, F, seed):
        r = np.random.default_rng(seed)
        x = r.normal(size=(R, F)).astype(np.float32)
        m = r.random(R) > 0.5
        s, _, c = streaming_stats(jnp.asarray(x), jnp.asarray(m),
                                  interpret=True)
        np.testing.assert_allclose(
            np.asarray(s), (x * m[:, None]).sum(0), rtol=1e-4, atol=1e-4)
        assert float(c) == m.sum()

    def test_all_masked(self):
        x = rng.normal(size=(32, 16)).astype(np.float32)
        m = np.zeros(32, bool)
        s, sq, c = streaming_stats(jnp.asarray(x), jnp.asarray(m),
                                   interpret=True)
        assert float(c) == 0
        np.testing.assert_array_equal(np.asarray(s), 0)

    def test_mapreduce_program_agrees_with_jnp_mean(self):
        from repro.core.mapreduce import MapReduceEngine
        from repro.utils import make_mesh
        x = rng.normal(size=(60, 24)).astype(np.float32)
        mesh = make_mesh((jax.device_count(),), ("data",))
        D = mesh.shape["data"]
        vals = x.reshape(D, 60 // D, 24)
        valid = np.ones((D, 60 // D), bool)
        res, _ = MapReduceEngine(mesh).run(
            KernelMeanProgram(interpret=True), jnp.asarray(vals),
            jnp.asarray(valid), 10)
        np.testing.assert_allclose(np.asarray(res), x.mean(0), atol=1e-5)


class TestFlashAttention:
    @pytest.mark.parametrize("B,H,Hkv,Sq,Skv,D", [
        (1, 2, 2, 128, 128, 64),
        (2, 4, 2, 128, 128, 64),
        (1, 8, 1, 256, 256, 32),   # MQA
        (1, 4, 2, 96, 96, 64),     # non-multiple of block
        (2, 4, 4, 64, 256, 128),   # cross/long kv
    ])
    def test_matches_ref_causal(self, B, H, Hkv, Sq, Skv, D):
        if Sq != Skv:
            pytest.skip("causal requires square") if False else None
        q = jnp.asarray(rng.normal(size=(B, H, Sq, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, Hkv, Skv, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, Hkv, Skv, D)).astype(np.float32))
        causal = Sq == Skv
        out = flash_attention(q, k, v, scale=D ** -0.5, causal=causal)
        ref = attention_ref(q, k, v, scale=D ** -0.5, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("window", [32, 64, 127])
    def test_sliding_window(self, window):
        B, H, S, D = 1, 2, 256, 64
        q = jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
        out = flash_attention(q, k, v, scale=D ** -0.5, window=window)
        ref = attention_ref(q, k, v, scale=D ** -0.5, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_bf16_inputs(self):
        B, H, S, D = 1, 2, 128, 64
        q = jnp.asarray(rng.normal(size=(B, H, S, D))).astype(jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(B, H, S, D))).astype(jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(B, H, S, D))).astype(jnp.bfloat16)
        out = flash_attention(q, k, v, scale=D ** -0.5)
        ref = attention_ref(q, k, v, scale=D ** -0.5)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=2e-2, atol=2e-2)


class TestSSDScan:
    @pytest.mark.parametrize("B,L,H,P,N,chunk", [
        (1, 64, 1, 16, 16, 16),
        (2, 128, 2, 32, 16, 64),
        (1, 128, 4, 64, 64, 128),  # mamba2-native dims
        (1, 100, 2, 32, 32, 32),   # padding path
    ])
    def test_matches_sequential(self, B, L, H, P, N, chunk):
        x = jnp.asarray(rng.normal(size=(B, L, H, P)).astype(np.float32)) * 0.5
        a = jnp.asarray(rng.uniform(0.7, 0.999, (B, L, H)).astype(np.float32))
        Bm = jnp.asarray(rng.normal(size=(B, L, N)).astype(np.float32)) * 0.3
        Cm = jnp.asarray(rng.normal(size=(B, L, N)).astype(np.float32)) * 0.3
        y, s = ssd_scan(x, a, Bm, Cm, chunk=chunk)
        y_ref, s_ref = ssd_scan(x, a, Bm, Cm, impl="ref")
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_chunk_invariance(self):
        B, L, H, P, N = 1, 128, 2, 16, 16
        x = jnp.asarray(rng.normal(size=(B, L, H, P)).astype(np.float32))
        a = jnp.asarray(rng.uniform(0.8, 0.999, (B, L, H)).astype(np.float32))
        Bm = jnp.asarray(rng.normal(size=(B, L, N)).astype(np.float32))
        Cm = jnp.asarray(rng.normal(size=(B, L, N)).astype(np.float32))
        outs = [np.asarray(ssd_scan(x, a, Bm, Cm, chunk=c)[0])
                for c in (16, 32, 64, 128)]
        for o in outs[1:]:
            np.testing.assert_allclose(outs[0], o, rtol=1e-4, atol=1e-4)

    def test_long_decay_stability(self):
        """Strong decay over a long sequence: state must not blow up."""
        B, L, H, P, N = 1, 256, 1, 16, 16
        x = jnp.ones((B, L, H, P), jnp.float32)
        a = jnp.full((B, L, H), 0.5, jnp.float32)
        Bm = jnp.ones((B, L, N), jnp.float32) * 0.1
        Cm = jnp.ones((B, L, N), jnp.float32) * 0.1
        y, s = ssd_scan(x, a, Bm, Cm, chunk=64)
        assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(s).all())
        # geometric series bound: |state| <= inp/(1-a)
        assert float(jnp.abs(np.asarray(s)).max()) < 2 * 0.1 * 1.0 / 0.5
