"""Filtering stage (paper Alg. 1): cosine weighting + 1-D ramp convolution.

Q_i(j, .) = (E_i * F_cos)(j, .)  (x)  F_ramp        for every detector row j

The ramp filter is applied per detector row via real FFT (Convolution Theorem,
§2.2.3), with the discrete band-limited ramp kernel of Kak & Slaney (ch. 3,
eq. 61) sampled at the virtual-detector pitch, optionally apodized
(shepp-logan / hann / hamming windows — the paper notes the window shape
affects image quality but not compute intensity).

The paper runs this stage on CPUs (IPP) to overlap with GPU back-projection;
on TPU it is a (cheap) jnp program fused into the pipelined reconstruction —
see DESIGN.md §2 for the rationale.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .geometry import CBCTGeometry

Array = jax.Array

_WINDOWS = ("ramlak", "shepp-logan", "hann", "hamming")


def cosine_weights(g: CBCTGeometry) -> np.ndarray:
    """F_cos: the FDK cosine (Feldkamp) weighting table, shape (N_v, N_u).

    w(u, v) = d / sqrt(d^2 + p^2 + zeta^2) with (p, zeta) the virtual-detector
    (isocenter-rescaled) physical coordinates of the pixel.
    """
    cu = (g.n_u - 1) / 2.0
    cv = (g.n_v - 1) / 2.0
    p = (np.arange(g.n_u, dtype=np.float64) - cu) * g.tau_u
    zeta = (np.arange(g.n_v, dtype=np.float64) - cv) * g.tau_v
    pp, zz = np.meshgrid(p, zeta, indexing="xy")
    return (g.d / np.sqrt(g.d * g.d + pp * pp + zz * zz)).astype(np.float32)


def ramp_kernel(n: int, tau: float) -> np.ndarray:
    """Band-limited spatial-domain ramp h[n], length n (n even, circular).

    h[0] = 1/(4 tau^2); h[m] = -1/(m pi tau)^2 for odd m; 0 for even m != 0.
    Negative lags are wrapped (h[n-m] = h[m]).
    """
    h = np.zeros(n, dtype=np.float64)
    h[0] = 1.0 / (4.0 * tau * tau)
    m = np.arange(1, n // 2 + 1)
    odd = m[m % 2 == 1]
    val = -1.0 / (odd * np.pi * tau) ** 2
    h[odd] = val
    h[n - odd] = val
    return h


def ramp_frequency_response(g: CBCTGeometry, window: str = "ramlak",
                            pad: int | None = None) -> np.ndarray:
    """rfft of the (apodized) ramp kernel at padded length."""
    if window not in _WINDOWS:
        raise ValueError(f"unknown window {window!r}; choose from {_WINDOWS}")
    n = pad or fft_length(g.n_u)
    h = ramp_kernel(n, g.tau_u)
    hf = np.fft.rfft(h)
    freq = np.fft.rfftfreq(n)  # cycles/sample in [0, 0.5]
    if window == "shepp-logan":
        x = np.pi * freq
        w = np.where(freq > 0, np.sin(np.clip(x, 1e-12, None)) / np.clip(x, 1e-12, None), 1.0)
    elif window == "hann":
        w = 0.5 * (1.0 + np.cos(2.0 * np.pi * freq))
    elif window == "hamming":
        w = 0.54 + 0.46 * np.cos(2.0 * np.pi * freq)
    else:
        w = np.ones_like(freq)
    return (hf * w).astype(np.complex64)


def fft_length(n_u: int) -> int:
    """Next power of two >= 2*N_u (linear, not circular, convolution)."""
    n = 1
    while n < 2 * n_u:
        n *= 2
    return n


@partial(jax.jit, static_argnames=("pad", "out_dtype"))
def _filter_batch(proj: Array, fcos: Array, hf: Array, pad: int, tau_u: float,
                  out_dtype=None) -> Array:
    """Alg. 1 over a batch: proj (B, N_v, N_u) -> filtered (B, N_v, N_u)."""
    n_u = proj.shape[-1]
    e = proj.astype(jnp.float32) * fcos[None]
    ef = jnp.fft.rfft(e, n=pad, axis=-1)
    q = jnp.fft.irfft(ef * hf[None, None, :], n=pad, axis=-1)[..., :n_u]
    # Discrete convolution sum approximates the integral: multiply by the
    # sample pitch tau (Kak & Slaney eq. 3.62).
    return (q * tau_u).astype(out_dtype or proj.dtype)


# Projections one FFT step filters. The padded planes and the spectrum of
# one projection take ~20x its f32 bytes on XLA:TPU (47 MB at a 768^2
# detector), so a whole scan filtered in one step would hold tens of GB;
# chunks of this size bound that to under 1 GB whatever the batch.
FILTER_CHUNK = 16


def _chunk(n: int) -> int:
    """The largest divisor of n that is <= FILTER_CHUNK (no padding copy)."""
    return max(c for c in range(1, min(n, FILTER_CHUNK) + 1) if n % c == 0)


def make_filter(g: CBCTGeometry, window: str = "ramlak", out_dtype=None):
    """Returns filter_fn(proj: (B, N_v, N_u)) -> (B, N_v, N_u), plus tables.

    `out_dtype` is the *storage* dtype of the emitted filtered projections
    (the precision policy's half-width stream, see core/precision.py); the
    FFT convolution itself always runs in f32. None keeps the input dtype.
    Batches larger than FILTER_CHUNK are filtered chunk by chunk in a
    sequential loop; every detector row sees the same FFT either way.
    """
    pad = fft_length(g.n_u)
    fcos = jnp.asarray(cosine_weights(g))
    hf = jnp.asarray(ramp_frequency_response(g, window, pad))
    out_dtype = jnp.dtype(out_dtype) if out_dtype is not None else None

    def filter_batch(proj: Array) -> Array:
        return _filter_batch(proj, fcos, hf, pad, g.tau_u, out_dtype)

    def filter_fn(proj: Array) -> Array:
        n = proj.shape[0]
        c = _chunk(n)
        if c == n:
            return filter_batch(proj)
        chunks = proj.reshape((n // c, c) + proj.shape[1:])
        out = jax.lax.map(filter_batch, chunks)
        return out.reshape((n,) + out.shape[2:])

    return filter_fn


def filter_projections(g: CBCTGeometry, proj: Array,
                       window: str = "ramlak", out_dtype=None) -> Array:
    """One-shot filtering of all projections (N_p, N_v, N_u)."""
    return make_filter(g, window, out_dtype)(proj)
