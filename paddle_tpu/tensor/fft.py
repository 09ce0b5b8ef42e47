"""Discrete Fourier transforms (parity surface: upstream python/paddle/fft.py).

Paddle's fft namespace is a thin convention layer (``n``/``axis``/``norm``
keyword names, hermitian variants) over the backend FFT. On TPU the backend
is XLA's FftOp — batched, fused into surrounding elementwise work, and
differentiable through jax — so every function here is a calling-convention
shim over ``jnp.fft``. No custom kernels: FFT is one of the ops XLA already
lowers well, and a Pallas rewrite would have to re-derive Cooley-Tukey for
the MXU with no expected win.
"""

from __future__ import annotations

import jax.numpy as jnp

__all__ = [
    "fft", "ifft", "fft2", "ifft2", "fftn", "ifftn",
    "rfft", "irfft", "rfft2", "irfft2", "rfftn", "irfftn",
    "hfft", "ihfft",
    "fftfreq", "rfftfreq", "fftshift", "ifftshift",
]

_NORMS = ("backward", "ortho", "forward")


def _norm(norm):
    norm = norm or "backward"
    if norm not in _NORMS:
        raise ValueError(f"norm must be one of {_NORMS}, got {norm!r}")
    return norm


def fft(x, n=None, axis=-1, norm="backward"):
    return jnp.fft.fft(x, n=n, axis=axis, norm=_norm(norm))


def ifft(x, n=None, axis=-1, norm="backward"):
    return jnp.fft.ifft(x, n=n, axis=axis, norm=_norm(norm))


def fft2(x, s=None, axes=(-2, -1), norm="backward"):
    return jnp.fft.fft2(x, s=s, axes=axes, norm=_norm(norm))


def ifft2(x, s=None, axes=(-2, -1), norm="backward"):
    return jnp.fft.ifft2(x, s=s, axes=axes, norm=_norm(norm))


def fftn(x, s=None, axes=None, norm="backward"):
    return jnp.fft.fftn(x, s=s, axes=axes, norm=_norm(norm))


def ifftn(x, s=None, axes=None, norm="backward"):
    return jnp.fft.ifftn(x, s=s, axes=axes, norm=_norm(norm))


def rfft(x, n=None, axis=-1, norm="backward"):
    return jnp.fft.rfft(x, n=n, axis=axis, norm=_norm(norm))


def irfft(x, n=None, axis=-1, norm="backward"):
    return jnp.fft.irfft(x, n=n, axis=axis, norm=_norm(norm))


def rfft2(x, s=None, axes=(-2, -1), norm="backward"):
    return jnp.fft.rfft2(x, s=s, axes=axes, norm=_norm(norm))


def irfft2(x, s=None, axes=(-2, -1), norm="backward"):
    return jnp.fft.irfft2(x, s=s, axes=axes, norm=_norm(norm))


def rfftn(x, s=None, axes=None, norm="backward"):
    return jnp.fft.rfftn(x, s=s, axes=axes, norm=_norm(norm))


def irfftn(x, s=None, axes=None, norm="backward"):
    return jnp.fft.irfftn(x, s=s, axes=axes, norm=_norm(norm))


def hfft(x, n=None, axis=-1, norm="backward"):
    return jnp.fft.hfft(x, n=n, axis=axis, norm=_norm(norm))


def ihfft(x, n=None, axis=-1, norm="backward"):
    return jnp.fft.ihfft(x, n=n, axis=axis, norm=_norm(norm))


def fftfreq(n, d=1.0, dtype=None):
    out = jnp.fft.fftfreq(n, d=d)
    return out.astype(dtype) if dtype is not None else out


def rfftfreq(n, d=1.0, dtype=None):
    out = jnp.fft.rfftfreq(n, d=d)
    return out.astype(dtype) if dtype is not None else out


def fftshift(x, axes=None):
    return jnp.fft.fftshift(x, axes=axes)


def ifftshift(x, axes=None):
    return jnp.fft.ifftshift(x, axes=axes)
