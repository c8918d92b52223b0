"""Pallas TPU kernel: 2-D (I)FFT of small tiles as DFT matmuls.

For the paper's tile sizes (K = 8 or 16) an FFT butterfly network is the
wrong tool on TPU — the MXU prefers the dense DFT form

    Y = W X W^T,      W[j, k] = exp(-2*pi*i*j*k / K)

which for a batch of B tiles is a pair of small GEMMs packed as
[K, B*K] matrices.  The forward transform maps real tiles to complex
(re, im) planes; the inverse returns the real part only (the spectral
conv consumes Re(IFFT)).

This replaces the FPGA's dedicated 2-D FFT pipeline stage (paper Fig 1)
with MXU work that fuses into the Hadamard stage's pipeline.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def dft_matrices(fft_size: int) -> tuple[np.ndarray, np.ndarray]:
    """Real/imag parts of the DFT matrix W = exp(-2 pi i jk / K)."""
    j, k = np.meshgrid(np.arange(fft_size), np.arange(fft_size),
                       indexing="ij")
    theta = 2.0 * np.pi * j * k / fft_size
    return (np.cos(theta).astype(np.float32),
            (-np.sin(theta)).astype(np.float32))


def _tile_matmul(w, a):
    """``w @ a[b]`` for every tile b: [K, K] x [B, K, K] -> [B, K, K], as
    one batched MXU matmul (no reshape moves data across lanes)."""
    wb = jnp.broadcast_to(w[None], a.shape[:1] + w.shape)
    return jax.lax.dot_general(wb, a, (((2,), (1,)), ((0,), (0,))),
                               preferred_element_type=jnp.float32)


def _fft_kernel(x_ref, cr_ref, ci_ref, yr_ref, yi_ref, *, k: int, bb: int):
    x = x_ref[...].reshape(bb * k, k)          # [B*K, K]
    cr, ci = cr_ref[...], ci_ref[...]
    # stage 1: A = X @ W^T   (X real)
    ar = jnp.dot(x, cr.T, preferred_element_type=jnp.float32)
    ai = jnp.dot(x, ci.T, preferred_element_type=jnp.float32)
    ar, ai = ar.reshape(bb, k, k), ai.reshape(bb, k, k)
    # stage 2: Y = W @ A  per tile
    yr_ref[...] = _tile_matmul(cr, ar) - _tile_matmul(ci, ai)
    yi_ref[...] = _tile_matmul(cr, ai) + _tile_matmul(ci, ar)


def _ifft_kernel(xr_ref, xi_ref, vr_ref, vi_ref, y_ref, *, k: int, bb: int):
    xr = xr_ref[...].reshape(bb * k, k)
    xi = xi_ref[...].reshape(bb * k, k)
    vr, vi = vr_ref[...], vi_ref[...]
    # stage 1: A = X @ V^T  (X complex)
    ar = (jnp.dot(xr, vr.T, preferred_element_type=jnp.float32)
          - jnp.dot(xi, vi.T, preferred_element_type=jnp.float32))
    ai = (jnp.dot(xr, vi.T, preferred_element_type=jnp.float32)
          + jnp.dot(xi, vr.T, preferred_element_type=jnp.float32))
    ar, ai = ar.reshape(bb, k, k), ai.reshape(bb, k, k)
    # stage 2: y = Re(V @ A)
    y_ref[...] = _tile_matmul(vr, ar) - _tile_matmul(vi, ai)


def _pad_batch(x: Array, bb: int) -> tuple[Array, int]:
    b = x.shape[0]
    rem = (-b) % bb
    if rem:
        x = jnp.pad(x, ((0, rem),) + ((0, 0),) * (x.ndim - 1))
    return x, b


@functools.partial(jax.jit, static_argnames=("fft_size", "block_b",
                                              "interpret"))
def fft2_tiles(x: Array, *, fft_size: int, block_b: int = 256,
               interpret: bool = True) -> tuple[Array, Array]:
    """[B, t, t] real tiles (t <= K, zero-padded here) -> [B, K, K] planes."""
    k = fft_size
    b, t, _ = x.shape
    if t < k:
        x = jnp.pad(x, ((0, 0), (0, k - t), (0, k - t)))
    x, b_orig = _pad_batch(x, block_b)
    grid = (x.shape[0] // block_b,)
    cr, ci = (jnp.asarray(a) for a in dft_matrices(k))
    spec_x = pl.BlockSpec((block_b, k, k), lambda i: (i, 0, 0))
    spec_d = pl.BlockSpec((k, k), lambda i: (0, 0))
    yr, yi = pl.pallas_call(
        functools.partial(_fft_kernel, k=k, bb=block_b),
        grid=grid,
        in_specs=[spec_x, spec_d, spec_d],
        out_specs=[spec_x, spec_x],
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x.astype(jnp.float32), cr, ci)
    return yr[:b_orig], yi[:b_orig]


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def ifft2_tiles(yr: Array, yi: Array, *, block_b: int = 256,
                interpret: bool = True) -> Array:
    """[B, K, K] complex planes -> [B, K, K] real (Re of the 2-D IFFT)."""
    k = yr.shape[-1]
    yr, b_orig = _pad_batch(yr, block_b)
    yi, _ = _pad_batch(yi, block_b)
    cr, ci = dft_matrices(k)
    vr = jnp.asarray(cr / k)
    vi = jnp.asarray(-ci / k)
    grid = (yr.shape[0] // block_b,)
    spec_x = pl.BlockSpec((block_b, k, k), lambda i: (i, 0, 0))
    spec_d = pl.BlockSpec((k, k), lambda i: (0, 0))
    y = pl.pallas_call(
        functools.partial(_ifft_kernel, k=k, bb=block_b),
        grid=grid,
        in_specs=[spec_x, spec_x, spec_d, spec_d],
        out_specs=spec_x,
        out_shape=jax.ShapeDtypeStruct(yr.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(yr.astype(jnp.float32), yi.astype(jnp.float32), vr, vi)
    return y[:b_orig]
