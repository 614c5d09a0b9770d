"""paddle.fft — discrete Fourier transforms.

Reference: python/paddle/fft.py (wraps fft C++ kernels over pocketfft/cuFFN
on CPU, cuFFT on GPU); TPU-native: XLA's FFT HLO via jnp.fft, generated from
ops/ops.yaml.
"""
from __future__ import annotations

import jax.numpy as jnp

from .core.tensor import Tensor
from .ops.generated_fft import *  # noqa: F401,F403
from .ops.generated_fft import __all__ as _gen_all


def fftfreq(n, d=1.0, dtype=None, name=None):
    return Tensor(jnp.fft.fftfreq(n, d=d).astype(dtype or "float32"))


def rfftfreq(n, d=1.0, dtype=None, name=None):
    return Tensor(jnp.fft.rfftfreq(n, d=d).astype(dtype or "float32"))


__all__ = list(_gen_all) + ["fftfreq", "rfftfreq"]
