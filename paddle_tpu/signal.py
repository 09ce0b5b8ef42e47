"""Short-time Fourier transforms (parity surface: upstream python/paddle/signal.py).

``stft``/``istft`` with paddle's conventions (frame_length/hop_length,
center padding, onesided default, window broadcast). Framing is expressed
as a gather over a precomputed (static) frame-index matrix rather than a
Python loop — under jit the gather plus batched ``rfft`` is two XLA HLOs,
batched over channels on the MXU-adjacent vector units; a per-frame
``lax.scan`` would serialize what is naturally one batched FFT.
"""

from __future__ import annotations

import jax.numpy as jnp

from .tensor import fft as _fft

__all__ = ["stft", "istft"]


def _frame_indices(n_samples: int, n_fft: int, hop: int):
    n_frames = 1 + (n_samples - n_fft) // hop
    if n_frames < 1:
        raise ValueError(
            f"signal length {n_samples} shorter than one n_fft={n_fft} frame")
    return (jnp.arange(n_frames)[:, None] * hop
            + jnp.arange(n_fft)[None, :])          # (n_frames, n_fft)


def stft(x, n_fft, hop_length=None, win_length=None, window=None,
         center=True, pad_mode="reflect", normalized=False, onesided=True):
    """paddle.signal.stft. x: (..., seq_len) real or complex.

    Returns (..., n_fft//2+1 or n_fft, n_frames) complex, matching the
    reference's output layout (freq before frames).
    """
    hop_length = hop_length if hop_length is not None else n_fft // 4
    win_length = win_length if win_length is not None else n_fft
    if window is None:
        window = jnp.ones((win_length,), dtype=jnp.result_type(x, jnp.float32))
    if win_length < n_fft:  # paddle zero-pads the window to n_fft, centered
        lpad = (n_fft - win_length) // 2
        window = jnp.pad(window, (lpad, n_fft - win_length - lpad))

    if center:
        pad = [(0, 0)] * (x.ndim - 1) + [(n_fft // 2, n_fft // 2)]
        x = jnp.pad(x, pad, mode=pad_mode)

    idx = _frame_indices(x.shape[-1], n_fft, hop_length)
    frames = x[..., idx] * window                  # (..., n_frames, n_fft)
    if jnp.iscomplexobj(x):
        onesided = False
    spec = (_fft.rfft(frames, axis=-1) if onesided
            else _fft.fft(frames, axis=-1))        # (..., n_frames, n_freq)
    if normalized:
        spec = spec / jnp.sqrt(jnp.asarray(n_fft, spec.real.dtype))
    return jnp.swapaxes(spec, -1, -2)              # (..., n_freq, n_frames)


def istft(x, n_fft, hop_length=None, win_length=None, window=None,
          center=True, normalized=False, onesided=True, length=None,
          return_complex=False):
    """paddle.signal.istft — overlap-add inverse with window-envelope
    normalization (the standard NOLA reconstruction)."""
    hop_length = hop_length if hop_length is not None else n_fft // 4
    win_length = win_length if win_length is not None else n_fft
    if window is None:
        window = jnp.ones((win_length,), dtype=jnp.float32)
    if win_length < n_fft:
        lpad = (n_fft - win_length) // 2
        window = jnp.pad(window, (lpad, n_fft - win_length - lpad))

    spec = jnp.swapaxes(x, -1, -2)                 # (..., n_frames, n_freq)
    if normalized:
        spec = spec * jnp.sqrt(jnp.asarray(n_fft, spec.real.dtype))
    frames = (_fft.irfft(spec, n=n_fft, axis=-1) if onesided
              else _fft.ifft(spec, n=n_fft, axis=-1))
    if not return_complex:
        frames = frames.real if jnp.iscomplexobj(frames) else frames
    frames = frames * window                       # (..., n_frames, n_fft)

    n_frames = frames.shape[-2]
    out_len = n_fft + hop_length * (n_frames - 1)
    idx = _frame_indices(out_len, n_fft, hop_length)   # (n_frames, n_fft)
    batch_shape = frames.shape[:-2]
    flat = frames.reshape((-1, n_frames, n_fft))
    sig = jnp.zeros((flat.shape[0], out_len), dtype=flat.dtype)
    sig = sig.at[:, idx].add(flat)                 # overlap-add
    env = jnp.zeros((out_len,), dtype=window.dtype).at[idx].add(window ** 2)
    sig = sig / jnp.where(env > 1e-11, env, 1.0)
    sig = sig.reshape(batch_shape + (out_len,))

    if center:
        sig = sig[..., n_fft // 2: out_len - n_fft // 2]
    if length is not None:
        sig = (sig[..., :length] if sig.shape[-1] >= length
               else jnp.pad(sig, [(0, 0)] * (sig.ndim - 1)
                            + [(0, length - sig.shape[-1])]))
    return sig
