"""Filtering stage (Alg. 1): ramp kernel, windows, FFT convolution."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core.filtering import (
    cosine_weights, fft_length, filter_projections, make_filter,
    ramp_frequency_response, ramp_kernel,
)
from repro.core.geometry import default_geometry


class TestRampKernel:
    def test_kak_slaney_values(self):
        tau = 0.5
        h = ramp_kernel(16, tau)
        assert h[0] == pytest.approx(1 / (4 * tau * tau))
        assert h[2] == 0.0 and h[4] == 0.0
        assert h[1] == pytest.approx(-1 / (np.pi * tau) ** 2)
        assert h[3] == pytest.approx(-1 / (3 * np.pi * tau) ** 2)
        # wrapped negative lags
        assert h[15] == h[1] and h[13] == h[3]

    def test_dc_is_suppressed(self):
        """The ramp filter kills constant signals: DC of the truncated
        kernel is small and decays ~1/N with kernel length."""
        h256 = ramp_kernel(256, 1.0)
        h1k = ramp_kernel(1024, 1.0)
        assert abs(h256.sum()) < 5e-3 * abs(h256[0])
        assert abs(h1k.sum()) < 0.3 * abs(h256.sum())

    def test_fft_length(self):
        assert fft_length(64) == 128
        assert fft_length(65) == 256
        assert fft_length(100) == 256


class TestWindows:
    @pytest.mark.parametrize("window", ["ramlak", "shepp-logan", "hann",
                                        "hamming"])
    def test_windows_real_and_bounded(self, window):
        g = default_geometry(16, n_proj=4)
        hf = ramp_frequency_response(g, window)
        assert hf.dtype == np.complex64
        ramlak = ramp_frequency_response(g, "ramlak")
        assert np.all(np.abs(hf) <= np.abs(ramlak) + 1e-5)

    def test_unknown_window_raises(self):
        g = default_geometry(16, n_proj=4)
        with pytest.raises(ValueError):
            ramp_frequency_response(g, "lanczos")


class TestFiltering:
    def test_constant_rows_filter_to_near_zero(self):
        g = default_geometry(64, n_proj=4)
        proj = jnp.ones(g.proj_shape(), jnp.float32)
        q = filter_projections(g, proj)
        # interior of a constant row is ~0 after the ramp (edges ring);
        # the truncation tail shrinks with detector width
        inner = q[..., g.n_u // 4: -g.n_u // 4]
        assert float(jnp.max(jnp.abs(inner))) < 0.05 * float(
            jnp.max(jnp.abs(q))
        )

    def test_linearity(self):
        g = default_geometry(16, n_proj=2)
        k1, k2 = jnp.ones(g.proj_shape()), 0.0
        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.normal(size=g.proj_shape()), jnp.float32)
        b = jnp.asarray(rng.normal(size=g.proj_shape()), jnp.float32)
        filt = make_filter(g)
        lhs = filt(2.0 * a + 3.0 * b)
        rhs = 2.0 * filt(a) + 3.0 * filt(b)
        np.testing.assert_allclose(np.array(lhs), np.array(rhs),
                                   rtol=1e-4, atol=1e-4)

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1))
    def test_filter_preserves_shape_and_finiteness(self, seed):
        g = default_geometry(12, n_proj=3)
        rng = np.random.default_rng(seed)
        proj = jnp.asarray(
            rng.uniform(0, 2, size=g.proj_shape()), jnp.float32
        )
        q = filter_projections(g, proj)
        assert q.shape == proj.shape
        assert bool(jnp.all(jnp.isfinite(q)))

    def test_cosine_weights_max_at_center(self):
        g = default_geometry(16, n_proj=2)
        w = cosine_weights(g)
        assert w.shape == (g.n_v, g.n_u)
        assert np.all(w <= 1.0 + 1e-6) and np.all(w > 0)
        cu, cv = (g.n_u - 1) // 2, (g.n_v - 1) // 2
        assert w[cv, cu] == w.max()


class TestChunkedFilter:
    """Batches above FILTER_CHUNK are filtered chunk by chunk: the FFT's
    working set is bounded, the result is the one-shot filter's."""

    @pytest.mark.parametrize("n_proj", [20, 32, 17])
    def test_chunked_equals_per_chunk(self, n_proj):
        from repro.core import filtering
        from repro.core.phantom import forward_project
        g = default_geometry(8, n_proj=n_proj)
        proj = forward_project(g)
        filt = make_filter(g)
        got = np.asarray(filt(proj))
        c = filtering._chunk(n_proj)
        assert n_proj % c == 0 and c <= filtering.FILTER_CHUNK
        want = np.concatenate([np.asarray(filt(proj[i:i + c]))
                               for i in range(0, n_proj, c)])
        np.testing.assert_array_equal(got, want)
