"""Public jit'd wrappers over the Pallas kernels.

``interpret`` defaults to True unless running on a real TPU backend —
the kernels are written for TPU (BlockSpec VMEM tiling, MXU-shaped
matmuls) and validated on CPU via the Pallas interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.scheduler import SCHEDULERS, build_tables
from repro.core.spectral import (SpectralGeometry, assemble_valid_tiles,
                                 extract_tiles_overlapping, make_geometry)
from repro.kernels import fft8, flash_attention as fa, ref
from repro.kernels import sparse_hadamard as sh
from repro.kernels import spectral_hadamard as shad

Array = jax.Array


def default_interpret() -> bool:
    """The one place that decides how a kernel runs when the caller did
    not say: compiled by Mosaic on a TPU backend (always), the Pallas
    interpreter everywhere else (CPU tests).  Code that must not run
    interpreted checks the platform itself (``chip_smoke.py``)."""
    return jax.default_backend() != "tpu"


def hadamard(w_f: Array, x_f: Array, *, flow: str = "output_stationary",
             block_n: int = 128, block_m: int = 128, block_p: int = 128,
             interpret: bool | None = None) -> Array:
    """Eq 3 via the Pallas kernel.

    w_f: complex [N, M, K, K];  x_f: complex [B, M, T, K, K]
    returns complex [B, N, T, K, K].
    """
    if interpret is None:
        interpret = default_interpret()
    b, m, t, kk, _ = x_f.shape
    n = w_f.shape[0]
    f = kk * kk
    wr = jnp.transpose(w_f.real.reshape(n, m, f), (2, 0, 1))
    wi = jnp.transpose(w_f.imag.reshape(n, m, f), (2, 0, 1))
    x = x_f.reshape(b, m, t, f)
    xr = jnp.transpose(x.real, (3, 1, 0, 2)).reshape(f, m, b * t)
    xi = jnp.transpose(x.imag, (3, 1, 0, 2)).reshape(f, m, b * t)
    yr, yi = shad.spectral_hadamard(
        wr.astype(jnp.float32), wi.astype(jnp.float32),
        xr.astype(jnp.float32), xi.astype(jnp.float32),
        flow=flow, block_n=block_n, block_m=block_m, block_p=block_p,
        interpret=interpret)
    y = (yr + 1j * yi).reshape(f, n, b, t)
    return jnp.transpose(y, (2, 1, 3, 0)).reshape(b, n, t, kk, kk)


def spectral_conv2d_pallas(x: Array, w_f: Array, geo: SpectralGeometry, *,
                           flow: str = "output_stationary",
                           interpret: bool | None = None) -> Array:
    """Full spectral conv forward on the Pallas path:
    fft8 -> spectral_hadamard -> fft8(inverse) -> valid-tile assembly.

    Overlap-save tiling, matching ``spectral_conv2d_pretransformed`` —
    the three pallas_calls round-trip spectral planes through HBM (the
    traffic the fused kernel eliminates) but compute the same function.
    """
    if interpret is None:
        interpret = default_interpret()
    b, m = x.shape[:2]
    n = w_f.shape[0]
    windows = extract_tiles_overlapping(x, geo)                 # [B,M,T,K,K]
    t = windows.shape[2]
    kk = geo.fft_size
    flat = windows.reshape(b * m * t, kk, kk)
    xr, xi = fft8.fft2_tiles(flat, fft_size=kk, interpret=interpret)
    x_f = (xr + 1j * xi).reshape(b, m, t, kk, kk)
    y_f = hadamard(w_f, x_f, flow=flow, interpret=interpret)
    y_flat = y_f.reshape(b * n * t, kk, kk)
    y_sp = fft8.ifft2_tiles(y_flat.real.astype(jnp.float32),
                            y_flat.imag.astype(jnp.float32),
                            interpret=interpret)
    ov = geo.ksize - 1
    y_tiles = y_sp.reshape(b, n, t, kk, kk)[..., ov:, ov:]
    return assemble_valid_tiles(y_tiles.astype(x.dtype), geo)


def scheduled_sparse_conv_group(sk_values, sk_indices, x_f: Array, *,
                                r: int = 10, method: str = "exact_cover",
                                interpret: bool | None = None
                                ) -> tuple[Array, dict]:
    """Sparse Hadamard for ONE group of N' kernels across all channels,
    executed through the exact-cover schedule's INDEX/VALUE tables.

    sk_values: complex [N', M, K, K]; sk_indices: int [N', M, nnz];
    x_f: complex [B=1 folded, M, T, K, K] -> returns [N', T, K, K] complex
    plus schedule stats.
    """
    import numpy as np
    if interpret is None:
        interpret = default_interpret()
    n_pe, m, kk, _ = sk_values.shape
    f = kk * kk
    vals = np.asarray(sk_values).reshape(n_pe, m, f)
    idx = np.asarray(sk_indices)
    fn = SCHEDULERS[method]
    tables = []
    cycles = 0
    ops = 0
    for mm in range(m):
        s = fn(idx[:, mm, :], f, r)
        tables.append(build_tables(s, vals[:, mm, :], idx[:, mm, :]))
        cycles += s.n_cycles
        ops += s.total_ops
    packed = sh.stack_tables(tables)

    b, _, t = x_f.shape[:3]
    assert b == 1
    x = x_f.reshape(m, t, f)
    xr = jnp.transpose(x.real, (0, 2, 1)).astype(jnp.float32)  # [M,F,T]
    xi = jnp.transpose(x.imag, (0, 2, 1)).astype(jnp.float32)
    yr, yi = sh.scheduled_sparse_hadamard(*packed, xr, xi,
                                          interpret=interpret)
    y = (yr + 1j * yi)                                          # [N',F,T]
    y = jnp.transpose(y, (0, 2, 1)).reshape(n_pe, t, kk, kk)
    stats = {"cycles": cycles, "ops": ops,
             "utilization": ops / max(1, cycles * n_pe)}
    return y, stats


def attention(q: Array, k: Array, v: Array, *, causal: bool = True,
              window: int | None = None, block_q: int = 128,
              block_k: int = 128, interpret: bool | None = None) -> Array:
    if interpret is None:
        interpret = default_interpret()
    return fa.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret)
