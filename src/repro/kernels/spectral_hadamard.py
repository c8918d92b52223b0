"""Pallas TPU kernel: frequency-binned batched complex GEMM (Eq 3).

The Hadamard-accumulate stage of a spectral conv layer is, per frequency
bin f:

    Y[f, n, p] = sum_m W[f, n, m] * X[f, m, p]          (complex)

i.e. a batch (over the K^2 frequency bins) of complex GEMMs contracting
input channels.  This is the TPU-native re-derivation of the paper's PE
array: on the FPGA each (kernel n, tile p) pair owns a scalar MAC PE and
channels stream serially (M' = 1) to avoid BRAM write conflicts; on TPU
the MXU wants the channel contraction inside the systolic array, so we
tile (n, p) across the grid and contract m in VMEM.

The paper's three dataflows map onto grid iteration orders (which operand
block stays resident in VMEM between grid steps):

  * ``output_stationary`` (= Flow-opt psum reuse): grid (F, n, p, m) with
    the contraction innermost; a float32 VMEM scratch accumulates the psum
    and HBM sees each output exactly once.
  * ``weight_stationary``  (= Flow #1, reuse kernels): grid (F, n, m, p);
    the W block's index map is constant in the inner p loop so Pallas keeps
    it resident, but psums must be read-modified-written in HBM once per m
    block — the Flow #3-like psum traffic the paper warns about.
  * ``input_stationary``   (= Flow #2, reuse activations): grid (F, p, m, n);
    X block resident across the n loop, same psum traffic.

Complex arithmetic uses the 3-multiplication Karatsuba form (real MXU
passes): m1 = ar.br, m2 = ai.bi, m3 = (ar+ai)(br+bi);
re = m1 - m2, im = m3 - m1 - m2.

Layouts are F-leading so the two minor dims of every block are the GEMM
dims (hardware-tileable 8x128 / 128x128):
  W: [F, N, M]   X: [F, M, P]   Y: [F, N, P]   (real+imag planes)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.dataflow import FLOWS

Array = jax.Array


def _karatsuba(wr, wi, xr, xi):
    m1 = jnp.dot(wr, xr, preferred_element_type=jnp.float32)
    m2 = jnp.dot(wi, xi, preferred_element_type=jnp.float32)
    m3 = jnp.dot(wr + wi, xr + xi, preferred_element_type=jnp.float32)
    return m1 - m2, m3 - m1 - m2


def _kernel_os(wr_ref, wi_ref, xr_ref, xi_ref, yr_ref, yi_ref,
               acc_r, acc_i, *, n_m_blocks: int):
    """Output-stationary: accumulate over the innermost m grid dim."""
    gm = pl.program_id(3)

    @pl.when(gm == 0)
    def _init():
        acc_r[...] = jnp.zeros_like(acc_r)
        acc_i[...] = jnp.zeros_like(acc_i)

    re, im = _karatsuba(wr_ref[0], wi_ref[0], xr_ref[0], xi_ref[0])
    acc_r[...] += re
    acc_i[...] += im

    @pl.when(gm == n_m_blocks - 1)
    def _flush():
        yr_ref[0] = acc_r[...]
        yi_ref[0] = acc_i[...]


def _kernel_rmw(wr_ref, wi_ref, xr_ref, xi_ref, yr_ref, yi_ref, *,
                m_axis: int):
    """Weight/input-stationary: psums read-modify-written across m blocks."""
    gm = pl.program_id(m_axis)
    re, im = _karatsuba(wr_ref[0], wi_ref[0], xr_ref[0], xi_ref[0])

    @pl.when(gm == 0)
    def _first():
        yr_ref[0] = re
        yi_ref[0] = im

    @pl.when(gm > 0)
    def _rest():
        yr_ref[0] += re
        yi_ref[0] += im


def _pad_to(x: Array, axis: int, mult: int) -> Array:
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


@functools.partial(
    jax.jit,
    static_argnames=("flow", "block_n", "block_m", "block_p", "interpret"))
def spectral_hadamard(wr: Array, wi: Array, xr: Array, xi: Array, *,
                      flow: str = "output_stationary",
                      block_n: int = 128, block_m: int = 128,
                      block_p: int = 128,
                      interpret: bool = True) -> tuple[Array, Array]:
    """Batched complex GEMM  Y[f,n,p] = sum_m W[f,n,m] X[f,m,p].

    wr/wi: [F, N, M], xr/xi: [F, M, P].  Returns (yr, yi): [F, N, P] f32.
    """
    if flow not in FLOWS:
        raise ValueError(f"flow must be one of {FLOWS}")
    f, n, m = wr.shape
    _, _, p = xr.shape
    bn, bm, bp = min(block_n, n), min(block_m, m), min(block_p, p)

    wr_, wi_ = (_pad_to(_pad_to(a, 1, bn), 2, bm) for a in (wr, wi))
    xr_, xi_ = (_pad_to(_pad_to(a, 1, bm), 2, bp) for a in (xr, xi))
    np_, mp_, pp_ = wr_.shape[1], wr_.shape[2], xr_.shape[2]
    gn, gm_, gp = np_ // bn, mp_ // bm, pp_ // bp

    out_shape = [jax.ShapeDtypeStruct((f, np_, pp_), jnp.float32)] * 2

    if flow == "output_stationary":
        grid = (f, gn, gp, gm_)
        w_map = lambda gf, a, b, c: (gf, a, c)
        x_map = lambda gf, a, b, c: (gf, c, b)
        y_map = lambda gf, a, b, c: (gf, a, b)
        kernel = functools.partial(_kernel_os, n_m_blocks=gm_)
        scratch = [pltpu.VMEM((bn, bp), jnp.float32)] * 2
        semantics = ("arbitrary", "parallel", "parallel", "arbitrary")
    elif flow == "weight_stationary":
        grid = (f, gn, gm_, gp)
        w_map = lambda gf, a, c, b: (gf, a, c)
        x_map = lambda gf, a, c, b: (gf, c, b)
        y_map = lambda gf, a, c, b: (gf, a, b)
        kernel = functools.partial(_kernel_rmw, m_axis=2)
        scratch = []
        semantics = ("arbitrary", "parallel", "arbitrary", "arbitrary")
    else:  # input_stationary
        grid = (f, gp, gm_, gn)
        w_map = lambda gf, b, c, a: (gf, a, c)
        x_map = lambda gf, b, c, a: (gf, c, b)
        y_map = lambda gf, b, c, a: (gf, a, b)
        kernel = functools.partial(_kernel_rmw, m_axis=2)
        scratch = []
        semantics = ("arbitrary", "parallel", "arbitrary", "arbitrary")

    w_spec = pl.BlockSpec((1, bn, bm), w_map)
    x_spec = pl.BlockSpec((1, bm, bp), x_map)
    y_spec = pl.BlockSpec((1, bn, bp), y_map)

    yr, yi = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[w_spec, w_spec, x_spec, x_spec],
        out_specs=[y_spec, y_spec],
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics),
        interpret=interpret,
    )(wr_, wi_, xr_, xi_)
    return yr[:, :n, :p], yi[:, :n, :p]
