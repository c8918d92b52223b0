"""Pallas TPU kernel executing the exact-cover schedule's INDEX/VALUE tables.

This is the TPU datapath for the paper's Fig 6 storage layout.  For one
group of N' sparse kernels, the scheduler (repro.core.scheduler) emits per
input channel m a table of T cycles:

  index_table[m, t, :]  r replica read addresses (frequency indices),
  sel[m, t, n]          which replica column feeds PE n,
  valid[m, t, n]        whether PE n is active,
  val_{r,i}[m, t, n]    the complex weight fed to PE n,
  out_index[m, t, n]    frequency bin PE n accumulates into.

On the FPGA each cycle performs r BRAM reads, a sel crossbar, N' scalar
MACs and a scatter into the psum buffer.  On TPU we execute the *same
tables* with MXU-native one-hot matmuls (gather == one-hot x X, routing ==
one-hot x gathered, scatter == outer product with the out-index one-hot),
vectorized over P parallel tiles and accumulated over channels in VMEM —
so the schedule's utilization win (T ~= nnz / (mu N') cycles instead of
K^2) becomes a work reduction rather than a port-conflict fix (DESIGN.md
hardware-adaptation notes).

Shapes:
  index_table int32 [M, T, r]; sel int32 [M, T, N']; valid f32 [M, T, N'];
  val_r/val_i f32 [M, T, N']; out_index int32 [M, T, N'];
  xr/xi f32 [M, F, P]   ->   yr/yi f32 [N', F, P]   (summed over M, T).

Since PR 4 the same datapath also runs INSIDE the fused conv kernel
(``fused_spectral_conv.fused_spectral_pipeline_scheduled``, Hadamard
mode 'scheduled'), between the in-kernel tile-FFT and IFFT/epilogue and
without the ``valid``/``out_index`` planes (see ``scheduler.LayerTables``).
This standalone kernel remains the direct Fig-6 table executor for an
externally-provided spectral input.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.dataflow import TPU_VMEM_LIMIT_BYTES
from repro.core.scheduler import ScheduleTables

Array = jax.Array


def _kernel(idx_ref, sel_ref, valid_ref, vr_ref, vi_ref, oidx_ref,
            xr_ref, xi_ref, yr_ref, yi_ref, acc_r, acc_i, *,
            n_cycles: int, n_channels: int, n_pe: int, f_dim: int, r: int):
    gm = pl.program_id(1)

    @pl.when(gm == 0)
    def _init():
        acc_r[...] = jnp.zeros_like(acc_r)
        acc_i[...] = jnp.zeros_like(acc_i)

    xr = xr_ref[0]            # [F, bp]
    xi = xi_ref[0]
    f_iota = jax.lax.broadcasted_iota(jnp.int32, (1, f_dim), 1)
    r_iota = jax.lax.broadcasted_iota(jnp.int32, (1, r), 1)

    def body(t, carry):
        ar, ai = carry
        # gather: one-hot [r, F] @ X [F, bp] -> replicas [r, bp]
        g = (idx_ref[0, t][:, None] == f_iota).astype(jnp.float32)
        rep_r = jnp.dot(g, xr, preferred_element_type=jnp.float32)
        rep_i = jnp.dot(g, xi, preferred_element_type=jnp.float32)
        # route: one-hot [N', r] @ replicas -> per-PE input [N', bp]
        s = (sel_ref[0, t][:, None] == r_iota).astype(jnp.float32)
        in_r = jnp.dot(s, rep_r, preferred_element_type=jnp.float32)
        in_i = jnp.dot(s, rep_i, preferred_element_type=jnp.float32)
        # complex MAC, masked by valid
        v = valid_ref[0, t][:, None]
        wr = vr_ref[0, t][:, None]
        wi = vi_ref[0, t][:, None]
        pr = v * (wr * in_r - wi * in_i)
        pi = v * (wr * in_i + wi * in_r)
        # scatter: outer product with out-index one-hot [N', F]
        o = (oidx_ref[0, t][:, None] == f_iota).astype(jnp.float32)
        ar = ar + o[:, :, None] * pr[:, None, :]
        ai = ai + o[:, :, None] * pi[:, None, :]
        return ar, ai

    ar, ai = jax.lax.fori_loop(0, n_cycles, body, (acc_r[...], acc_i[...]))
    acc_r[...] = ar
    acc_i[...] = ai

    @pl.when(gm == n_channels - 1)
    def _flush():
        yr_ref[...] = acc_r[...]
        yi_ref[...] = acc_i[...]


@functools.partial(jax.jit,
                   static_argnames=("block_p", "interpret"))
def scheduled_sparse_hadamard(index_table: Array, sel: Array, valid: Array,
                              val_r: Array, val_i: Array, out_index: Array,
                              xr: Array, xi: Array, *,
                              block_p: int = 128,
                              interpret: bool = True
                              ) -> tuple[Array, Array]:
    m, t, r = index_table.shape
    n_pe = sel.shape[2]
    _, f, p = xr.shape
    bp = min(block_p, p)
    rem = (-p) % bp
    if rem:
        xr = jnp.pad(xr, ((0, 0), (0, 0), (0, rem)))
        xi = jnp.pad(xi, ((0, 0), (0, 0), (0, rem)))
    gp = xr.shape[2] // bp

    tab_spec = lambda shape: pl.BlockSpec(
        (1,) + shape, lambda gpp, gm: (gm,) + (0,) * len(shape))
    x_spec = pl.BlockSpec((1, f, bp), lambda gpp, gm: (gm, 0, gpp))
    y_spec = pl.BlockSpec((n_pe, f, bp), lambda gpp, gm: (0, 0, gpp))

    kern = functools.partial(_kernel, n_cycles=t, n_channels=m,
                             n_pe=n_pe, f_dim=f, r=r)
    yr, yi = pl.pallas_call(
        kern,
        grid=(gp, m),
        in_specs=[tab_spec((t, r)), tab_spec((t, n_pe)), tab_spec((t, n_pe)),
                  tab_spec((t, n_pe)), tab_spec((t, n_pe)),
                  tab_spec((t, n_pe)), x_spec, x_spec],
        out_specs=[y_spec, y_spec],
        out_shape=[jax.ShapeDtypeStruct((n_pe, f, xr.shape[2]),
                                        jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((n_pe, f, bp), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=TPU_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(index_table, sel, valid.astype(jnp.float32), val_r, val_i,
      out_index, xr, xi)
    return yr, yi


def stack_tables(tables: list[ScheduleTables]
                 ) -> tuple[Array, Array, Array, Array, Array, Array]:
    """Stack per-channel ScheduleTables, padding to the max cycle count
    (padded cycles have valid == 0 and are inert)."""
    t_max = max(tb.n_cycles for tb in tables)
    n = tables[0].sel.shape[1]
    r = tables[0].index_table.shape[1]

    def pad(a, rows):
        return np.pad(a, ((0, rows - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))

    idx = np.stack([pad(tb.index_table, t_max) for tb in tables])
    sel = np.stack([pad(tb.sel, t_max) for tb in tables])
    valid = np.stack([pad(tb.valid, t_max) for tb in tables])
    vals = np.stack([pad(tb.values, t_max) for tb in tables])
    oidx = np.stack([pad(tb.out_index, t_max) for tb in tables])
    return (jnp.asarray(idx, jnp.int32), jnp.asarray(sel, jnp.int32),
            jnp.asarray(valid, jnp.float32),
            jnp.asarray(vals.real, jnp.float32),
            jnp.asarray(vals.imag, jnp.float32),
            jnp.asarray(oidx, jnp.int32))
