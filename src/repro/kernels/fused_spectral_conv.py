"""Pallas TPU kernel: ONE pallas_call for a whole spectral conv layer.

The staged Pallas path (``ops.spectral_conv2d_pallas``) launches three
kernels per layer — fft8 -> spectral_hadamard -> ifft8 — and round-trips
the complex spectral tensors through HBM between stages.  That
inter-stage traffic is the TPU analogue of the off-chip communication
the paper's dataflow eliminates (§4).  This kernel keeps the spectral
intermediates in VMEM: HBM sees only the input, the kernel operand and
the finished spatial output.

Per grid step the kernel performs, entirely in VMEM:

  1. windows  — the step's K x K overlap-save windows as ``[S=K^2, bm,
     bp]`` (s-leading, lanes = tiles).  Two sources
     (``dataflow.INPUT_MODES``):
       * 'windowed': a block of the host-materialized ``[S, M, P]``
         window tensor;
       * 'halo': the RAW NCHW activation, read through overlapping
         element-offset blocks (``pl.Element`` block dims) that cover
         ``bth`` tile rows x ``btw`` tile columns plus their shared k-1
         halo, clamped at the image edges.  Two exact one-hot gathers
         build the windows: per window row, a masked sum over the raw
         rows (``spectral.halo_gather_matrices``' ``gr``, on the VPU),
         then an MXU column gather whose output lanes are ``(kw, jj)``
         with the tile column ``jj`` padded to a whole lane tile
         (``LANES``).  All-zero selector rows are the 'same'
         zero-padding.
  2. tile-FFT — one MXU GEMM against D = kron(W, W)[active, :]
     ([Fa, K^2]) on the ``[S, bm * bp]`` view of the windows;
  3. Hadamard — the frequency-batched complex GEMM in 3-multiplication
     Karatsuba form over the Fa active bins.  The kernel operand has two
     sources (``dataflow.HADAMARD_MODES``): spectral planes ``[Fa, N,
     M]`` ('dense' / 'bin'), or the Alg-2 INDEX/VALUE tables
     ('scheduled'), which the kernel expands into the same planes in
     VMEM — per schedule cycle one one-hot matmul resolves each PE
     lane's frequency (``idx[sel]``) and the lane's weight is added
     there.  HBM then streams O(nnz) table words instead of O(Fa*N*M)
     plane words, and the MXU runs the dense GEMM;
  4. IFFT + epilogue — Re(Dinv @ Y~) with Dinv restricted to the t^2
     valid output rows and Fa active columns, then bias (+ residual
     shortcut) + ReLU.  Every window's t x t valid outputs are complete
     (overlap-save), so the non-linear epilogue is exact in-kernel.

The contraction over input channels M runs across a grid dimension; the
paper's three reuse choices map onto grid iteration orders:

  * ``output_stationary``  grid (n, p, m): f32 spectral psums accumulate
    in VMEM scratch across the innermost m loop; one output write.
  * ``weight_stationary``  grid (n, m, p) (Flow #1, reuse kernels): the
    kernel block is constant across the inner p loop; each m block's
    partial result is IFFT'd eagerly (IFFT is linear) and the SPATIAL
    psum is read-modify-written through the output buffer.
  * ``input_stationary``   grid (p, m, n) (Flow #2, reuse activations):
    the window block's FFT is computed once (n-block 0) into VMEM
    scratch and reused across the inner n loop; same spatial-psum RMW.

Output side: the output lives in ANY memory (HBM) and the kernel moves
its rectangles itself with ``pltpu.make_async_copy`` through
``dataflow.DMA_SLOTS`` double-buffered VMEM staging tiles.  The RMW
flows prefetch the accumulator rectangle before the step's compute and
add to it afterwards, so a revisit may come any number of grid steps
later.  Both input modes write the same ``[S2, N, lanes]`` tile stream;
the host relays it to the spatial canvas in one fused XLA copy together
with the 'same' crop.

TPU tiling: every block's lane (minor) dim is a multiple of 128 or the
array's extent and every sublane dim a multiple of 8 or the extent
(``dataflow.tiling_violations`` is the rule; Alg 1 searches only legal
blocks and ``resilience.validate_layer_plan`` rejects the rest).  The
kernel pads the tile (lane) axis to whole lane tiles itself.  All
in-kernel dots run at ``Precision.HIGHEST``: the one-hot gathers must be
exact, and the f32 result is compared with the f32 einsum oracle.

HBM traffic per flow is modeled by ``core.dataflow.tpu_fused_flow_cost``;
flow/blocks/modes are chosen per layer by ``core.autotune`` and compiled
into a ``core.plan.LayerPlan`` that ``execute_layer_plan`` consumes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import resilience as res
from repro.core import sparse as sp
from repro.core.dataflow import (DMA_SLOTS, FLOWS, INPUT_MODES, LANES,
                                 SUBLANES, TPU_VMEM_LIMIT_BYTES,
                                 lane_round)
from repro.core.spectral import (ROW_ALIGN, HaloGeometry, SpectralGeometry,
                                 assemble_tile_canvas, crop_canvas_same,
                                 extract_tiles_overlapping,
                                 halo_block_geometry, halo_gather_matrices)
from repro.kernels.fft8 import dft_matrices
from repro.kernels.ops import default_interpret

Array = jax.Array

_HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# DFT operators in flattened (kron) form, overlap-save + active-bin layout
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def overlap_save_operators(fft_size: int, ksize: int,
                           active: tuple[int, ...] | None = None
                           ) -> tuple[np.ndarray, ...]:
    """(dfr, dfi, dvr, dvi) for the fused kernel.

    dfr/dfi [Fa, K^2]: forward 2-D DFT on flattened K x K windows,
        rows restricted to the active frequency bins.
    dvr/dvi [t^2, Fa]: inverse 2-D DFT restricted to the t^2
        wraparound-free output rows (u, v in [k-1, K)) and the active
        columns — the only spectra Y~ can be non-zero at.
    """
    cr, ci = dft_matrices(fft_size)
    w = cr + 1j * ci
    d = np.kron(w, w)                                   # [K^2, K^2]
    winv = (cr - 1j * ci) / fft_size                    # conj(W) / K
    dv = np.kron(winv, winv)                            # [K^2, K^2]
    valid = [u * fft_size + v
             for u in range(ksize - 1, fft_size)
             for v in range(ksize - 1, fft_size)]
    dv = dv[valid]                                      # [t^2, K^2]
    if active is not None:
        a = np.asarray(active)
        d = d[a]
        dv = dv[:, a]
    return tuple(np.ascontiguousarray(p, np.float32)
                 for p in (d.real, d.imag, dv.real, dv.imag))


# ---------------------------------------------------------------------------
# Kernel stages
# ---------------------------------------------------------------------------

def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


_MM = (((1,), (0,)), ((), ()))              # [a, k] @ [k, b]
_BMM = (((2,), (1,)), ((0,), (0,)))         # batched [z, a, k] @ [z, k, b]
_BMM_TN = (((1,), (1,)), ((0,), (0,)))      # batched [z, k, a]^T @ [z, k, b]


def _tile_fft(x, dfr, dfi):
    """Stage 1: one GEMM against the kron'd DFT operator.
    [S, bm, bp] real windows -> (re, im) [Fa, bm, bp] spectral planes."""
    s, bm, bp = x.shape
    fa = dfr.shape[0]
    x2 = x.reshape(s, bm * bp)
    return (_dot(dfr, x2, _MM).reshape(fa, bm, bp),
            _dot(dfi, x2, _MM).reshape(fa, bm, bp))


def _hadamard(wr, wi, xfr, xfi, *, w_mn: bool):
    """Stage 2: frequency-batched Karatsuba complex GEMM over active bins.
    W [Fa, bn, bm] (or [Fa, bm, bn] when ``w_mn``) x X~ [Fa, bm, bp] ->
    (re, im) [Fa, bn, bp]."""
    dims = _BMM_TN if w_mn else _BMM
    m1 = _dot(wr, xfr, dims)
    m2 = _dot(wi, xfi, dims)
    m3 = _dot(wr + wi, xfr + xfi, dims)
    return m1 - m2, m3 - m1 - m2


def _ifft_real(re, im, dvr, dvi):
    """Stage 3: Re(Dinv @ Y~) -> [S2, bn, bp] finished spatial outputs."""
    fa, bn, bp = re.shape
    s2 = dvr.shape[0]
    y = (_dot(dvr, re.reshape(fa, bn * bp), _MM)
         - _dot(dvi, im.reshape(fa, bn * bp), _MM))
    return y.reshape(s2, bn, bp)


# ---------------------------------------------------------------------------
# Operand sources: where a grid step's windows and kernel planes come from
# ---------------------------------------------------------------------------
#
# A source wraps the step's input Refs; ``chunks()`` / ``read()`` trace
# the loads (and, for the halo gather / table expansion, the VMEM
# compute) at the call site.  The flow bodies call them where they need
# the value, so input-stationary's FFT-once guard also skips the halo
# gather on the n-block revisits.  Windows come as a list of
# [S, bm, LANES] chunks: every GEMM of the pipeline then has one lane
# tile of columns, whichever input mode or block_p produced them.

def _lanes(c: int) -> slice:
    return slice(c * LANES, (c + 1) * LANES)


class _WindowBlock:
    """'windowed': a [S, bm, bp] block of the host window tensor."""

    n_refs = 1

    def __init__(self, refs):
        (self._x,) = refs

    def chunks(self):
        return [self._x[:, :, _lanes(c)]
                for c in range(self._x.shape[2] // LANES)]


class _HaloBlock:
    """'halo': gather [S, bm, bth*lw] windows from a raw [1, bm, rh, rw]
    block.  The row selector ``gr`` [1, bth*K, rh, 1] (each one-hot row
    of ``halo_gather_matrices`` as a column, broadcast across lanes in
    the kernel) picks a window row's raw row as a masked sum over rh
    (zero rows = 'same' padding), giving [bm, rw] with channels on
    sublanes; the column
    selector ``gc`` [1, rw, K*lw] then picks the K columns of every
    window into lanes (kw, jj).  The [bm, lw] planes stack s-major (kh,
    kw) on a major axis — the windowed path's order, so both paths run
    the same GEMMs — and no step moves data between sublanes and a
    major axis.  Each tile row's lw lanes are whole chunks."""

    n_refs = 3

    def __init__(self, refs, *, fft_size: int, bth: int, lw: int):
        self._x, self._gr, self._gc = refs
        self._k, self._bth, self._lw = fft_size, bth, lw

    def chunks(self):
        k, lw = self._k, self._lw
        x = self._x[0]                                  # [bm, rh, rw]
        gc = self._gc[0]                                # [rw, K*lw]
        out = []
        for ii in range(self._bth):
            planes = []
            for kh in range(k):
                sel = self._gr[0, ii * k + kh]          # [rh, 1] one-hot
                row = jnp.sum(x * sel[None], axis=1)    # [bm, rw]
                g = _dot(row, gc, _MM)                  # [bm, (kw, jj)]
                planes += [g[:, kw * lw:(kw + 1) * lw] for kw in range(k)]
            win = jnp.stack(planes)                     # [(kh,kw), bm, lw]
            out += [win[:, :, _lanes(c)] for c in range(lw // LANES)]
        return out


class _PlaneBlock:
    """'dense' / 'bin': [Fa, bn, bm] spectral kernel plane blocks."""

    n_refs = 2
    w_mn = False

    def __init__(self, refs):
        self._wr, self._wi = refs

    def read(self):
        return self._wr[...], self._wi[...]


class _TableBlock:
    """'scheduled': expand one (group, channel-block) of Alg-2 tables —
    idx [1, bm, T, r], sel / vr / vi [1, bm, T, N'] — into kernel planes
    [Fa, bm, N'] in VMEM.  Cycle t contributes lane i's weight at the
    frequency its routed replica reads, ``idx[c, t, sel[c, t, i]]``
    (that is also where Fig 6 scatters the product); the one-hot
    ``[Fa, r] @ [r, N']`` product is that frequency's indicator.  Idle
    lanes and padded cycles/channels carry zero weight."""

    n_refs = 4
    w_mn = True

    def __init__(self, refs, *, fa: int):
        self._idx, self._sel, self._vr, self._vi = refs
        self._fa = fa

    def read(self):
        _, bm, n_cycles, r = self._idx.shape
        n_pe = self._sel.shape[3]
        fa = self._fa
        f_iota = jax.lax.broadcasted_iota(jnp.int32, (1, fa, 1), 1)
        r_iota = jax.lax.broadcasted_iota(jnp.int32, (1, r, 1), 1)

        def cycle(t, carry):
            wr, wi = carry
            row = lambda ref: ref[0, :, pl.ds(t, 1), :]  # [bm, 1, lanes]
            g = (row(self._idx) == f_iota).astype(jnp.float32)   # [bm,Fa,r]
            s = (row(self._sel) == r_iota).astype(jnp.float32)   # [bm,r,N']
            hit = _dot(g, s, _BMM)                               # [bm,Fa,N']
            return (wr + hit * row(self._vr), wi + hit * row(self._vi))

        zero = jnp.zeros((bm, fa, n_pe), jnp.float32)
        wr, wi = jax.lax.fori_loop(0, n_cycles, cycle, (zero, zero))
        return jnp.swapaxes(wr, 0, 1), jnp.swapaxes(wi, 0, 1)


# ---------------------------------------------------------------------------
# Manual-DMA output accumulators
# ---------------------------------------------------------------------------

class _TileSink:
    """Output layout [S2, Np, lanes]: rectangle (n, p) is the [S2, bn,
    bp] slab at (n*bn, p*bp).  ``sc`` is the optional residual shortcut
    Ref in the same layout, indexed on (n, p) only, so at the flush
    step (the only epilogue site) its block is this rectangle's; it is
    added after bias and before ReLU."""

    def __init__(self, bn: int, bp: int, sc=None):
        self.bn, self.bp, self.sc = bn, bp, sc

    def dst(self, y_hbm, n_idx, p_idx):
        return y_hbm.at[:, pl.ds(n_idx * self.bn, self.bn),
                        pl.ds(p_idx * self.bp, self.bp)]

    def epilogue(self, y, b_ref, relu: bool, c: int):
        """Bias (+ shortcut) (+ ReLU) on lane chunk ``c`` [S2, bn, LANES]."""
        y = y + b_ref[...][None]                        # bias [bn, 1]
        if self.sc is not None:
            y = y + self.sc[:, :, _lanes(c)]
        if relu:
            y = jnp.maximum(y, 0.0)
        return y


def _dma_slot():
    """Staging slot for this grid step: the linearized step index mod
    DMA_SLOTS, alternating VMEM tiles/semaphores between consecutive
    steps (double buffering)."""
    step = ((pl.program_id(0) * pl.num_programs(1) + pl.program_id(1))
            * pl.num_programs(2) + pl.program_id(2))
    return step % DMA_SLOTS


def _dma_rmw_start(dst, acc, sem, slot, gm):
    """RMW prologue: start the inbound accumulator DMA for a revisit
    step, BEFORE the step's compute — the copy-in overlaps the MXU work
    and ``_dma_rmw_finish`` waits on it only at accumulation time."""
    @pl.when(gm > 0)
    def _prefetch():
        pltpu.make_async_copy(dst, acc.at[slot], sem.at[slot]).start()


def _stage(acc, slot, ys, fn):
    """Write ``fn(c, y_c)`` into lane chunk c of staging slot ``slot``."""
    for c, y in enumerate(ys):
        acc[slot, :, :, _lanes(c)] = fn(c, y)


def _write_back(dst, acc, sem, slot):
    cp = pltpu.make_async_copy(acc.at[slot], dst, sem.at[slot])
    cp.start()
    cp.wait()


def _dma_rmw_finish(sink, dst, acc, sem, ys, b_ref, *, slot, gm,
                    n_m_blocks: int, relu: bool):
    """Spatial-psum RMW across the m grid axis through the manual-DMA
    accumulator (``ys``: the step's [S2, bn, LANES] chunks): first
    visit writes, middle visits add + write back, the final visit
    applies the epilogue.  Write-backs complete before the step ends,
    so a later revisit always reads finished data."""
    old = lambda c: acc[slot, :, :, _lanes(c)]
    if n_m_blocks == 1:
        _stage(acc, slot, ys,
               lambda c, y: sink.epilogue(y, b_ref, relu, c))
        _write_back(dst, acc, sem, slot)
        return
    last = n_m_blocks - 1

    @pl.when(gm == 0)
    def _first():
        _stage(acc, slot, ys, lambda c, y: y)
        _write_back(dst, acc, sem, slot)

    @pl.when((gm > 0) & (gm < last))
    def _mid():
        pltpu.make_async_copy(dst, acc.at[slot], sem.at[slot]).wait()
        _stage(acc, slot, ys, lambda c, y: old(c) + y)
        _write_back(dst, acc, sem, slot)

    @pl.when(gm == last)
    def _last():
        pltpu.make_async_copy(dst, acc.at[slot], sem.at[slot]).wait()
        _stage(acc, slot, ys,
               lambda c, y: sink.epilogue(old(c) + y, b_ref, relu, c))
        _write_back(dst, acc, sem, slot)


def _dma_flush(sink, dst, acc, sem, ys, b_ref, *, slot, relu: bool):
    """Output-stationary flush: one epilogued write per rectangle."""
    _stage(acc, slot, ys, lambda c, y: sink.epilogue(y, b_ref, relu, c))
    _write_back(dst, acc, sem, slot)


# ---------------------------------------------------------------------------
# Flow bodies
# ---------------------------------------------------------------------------

def _body_os(xs, ws, ops, b_ref, y_hbm, acc_r, acc_i, ydma, sem, *,
             n_m_blocks: int, relu: bool, sink):
    """Output-stationary, grid (n, p, m): spectral psums live in VMEM
    scratch across the innermost m grid dim; IFFT + epilogue + the
    single DMA write happen once, at the last m block."""
    dfr, dfi, dvr, dvi = ops
    gm = pl.program_id(2)
    slot = _dma_slot()
    dst = sink.dst(y_hbm, pl.program_id(0), pl.program_id(1))

    @pl.when(gm == 0)
    def _init():
        acc_r[...] = jnp.zeros_like(acc_r)
        acc_i[...] = jnp.zeros_like(acc_i)

    wr, wi = ws.read()
    for c, x in enumerate(xs.chunks()):
        re, im = _hadamard(wr, wi, *_tile_fft(x, dfr[...], dfi[...]),
                           w_mn=ws.w_mn)
        acc_r[:, :, _lanes(c)] += re
        acc_i[:, :, _lanes(c)] += im

    @pl.when(gm == n_m_blocks - 1)
    def _flush():
        ys = [_ifft_real(acc_r[:, :, _lanes(c)], acc_i[:, :, _lanes(c)],
                         dvr[...], dvi[...])
              for c in range(acc_r.shape[2] // LANES)]
        _dma_flush(sink, dst, ydma, sem, ys, b_ref, slot=slot, relu=relu)


def _body_ws(xs, ws, ops, b_ref, y_hbm, ydma, sem, *, n_m_blocks: int,
             relu: bool, sink):
    """Weight-stationary, grid (n, m, p): each m block's partial Y~ is
    IFFT'd eagerly and the real spatial psum is read-modify-written
    through the manual-DMA accumulator."""
    dfr, dfi, dvr, dvi = ops
    gm = pl.program_id(1)
    slot = _dma_slot()
    dst = sink.dst(y_hbm, pl.program_id(0), pl.program_id(2))
    _dma_rmw_start(dst, ydma, sem, slot, gm)
    wr, wi = ws.read()
    ys = [_ifft_real(*_hadamard(wr, wi, *_tile_fft(x, dfr[...], dfi[...]),
                                w_mn=ws.w_mn), dvr[...], dvi[...])
          for x in xs.chunks()]
    _dma_rmw_finish(sink, dst, ydma, sem, ys, b_ref, slot=slot, gm=gm,
                    n_m_blocks=n_m_blocks, relu=relu)


def _body_is(xs, ws, ops, b_ref, y_hbm, xfr_s, xfi_s, ydma, sem, *,
             n_m_blocks: int, relu: bool, sink):
    """Input-stationary, grid (p, m, n): the window block is constant
    across the inner n loop, so its FFT is computed once (n-block 0)
    into VMEM scratch and reused — the reuse the flow is named for."""
    dfr, dfi, dvr, dvi = ops
    gm = pl.program_id(1)
    gn = pl.program_id(2)
    slot = _dma_slot()
    dst = sink.dst(y_hbm, gn, pl.program_id(0))
    _dma_rmw_start(dst, ydma, sem, slot, gm)

    @pl.when(gn == 0)
    def _fft_once():
        for c, x in enumerate(xs.chunks()):
            xfr, xfi = _tile_fft(x, dfr[...], dfi[...])
            xfr_s[:, :, _lanes(c)] = xfr
            xfi_s[:, :, _lanes(c)] = xfi

    wr, wi = ws.read()
    ys = [_ifft_real(*_hadamard(wr, wi, xfr_s[:, :, _lanes(c)],
                                xfi_s[:, :, _lanes(c)], w_mn=ws.w_mn),
                     dvr[...], dvi[...])
          for c in range(xfr_s.shape[2] // LANES)]
    _dma_rmw_finish(sink, dst, ydma, sem, ys, b_ref, slot=slot, gm=gm,
                    n_m_blocks=n_m_blocks, relu=relu)


_BODIES = {"output_stationary": _body_os,
           "weight_stationary": _body_ws,
           "input_stationary": _body_is}


def _flow_layout(flow: str, gn: int, gm: int, gp: int):
    """(grid, canon, dimension_semantics) for a reuse flow.

    ``canon`` maps the flow's grid arguments back to canonical
    (n, p, m) block indices, so every operand's BlockSpec index map can
    be written once against the canonical order."""
    if flow == "output_stationary":
        grid = (gn, gp, gm)
        canon = lambda n, p, m: (n, p, m)
        semantics = ("parallel", "parallel", "arbitrary")
    elif flow == "weight_stationary":
        grid = (gn, gm, gp)
        canon = lambda n, m, p: (n, p, m)
        semantics = ("parallel", "arbitrary", "arbitrary")
    elif flow == "input_stationary":
        grid = (gp, gm, gn)
        canon = lambda p, m, n: (n, p, m)
        semantics = ("parallel", "arbitrary", "arbitrary")
    else:
        raise ValueError(f"flow must be one of {FLOWS}")
    return grid, canon, semantics


# ---------------------------------------------------------------------------
# pallas_call builder
# ---------------------------------------------------------------------------

def _pad_axis(x: Array, axis: int, mult: int) -> Array:
    rem = (-x.shape[axis]) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


def _fused_call(x_src, x_ops, x_specs, w_src, w_ops, w_specs, ops,
                bias_col, shortcut, *, flow, grid_counts, canon_layout,
                bn, bm, bp, np_, pp_, relu, interpret):
    """One pallas_call of the fused conv.  ``x_src``/``w_src`` are the
    window / kernel-plane source factories (bound to their static
    geometry), ``*_ops``/``*_specs`` their operands and BlockSpecs;
    ``shortcut`` is an optional [S2, np_, pp_] operand in the output
    layout.  Returns the [S2, np_, pp_] tile stream."""
    gn, gm, gp = grid_counts
    grid, canon, semantics = canon_layout
    fa, s = ops[0].shape
    s2 = ops[2].shape[0]
    n_x, n_w = (getattr(src, "func", src).n_refs for src in (x_src, w_src))
    has_sc = shortcut is not None

    def kernel(*refs):
        sc, refs = (refs[0], refs[1:]) if has_sc else (None, refs)
        xs = x_src(refs[:n_x])
        ws = w_src(refs[n_x:n_x + n_w])
        rest = refs[n_x + n_w:]
        ops_refs, b_ref, y_hbm, scratch = rest[:4], rest[4], rest[5], \
            rest[6:]
        _BODIES[flow](xs, ws, ops_refs, b_ref, y_hbm, *scratch,
                      n_m_blocks=gm, relu=relu,
                      sink=_TileSink(bn, bp, sc))

    const = lambda r, c: pl.BlockSpec((r, c), lambda *_: (0, 0))
    in_specs = (list(x_specs) + list(w_specs)
                + [const(fa, s), const(fa, s), const(s2, fa),
                   const(s2, fa),
                   pl.BlockSpec((bn, 1), lambda *g: (canon(*g)[0], 0))])
    operands = list(x_ops) + list(w_ops) + list(ops) + [bias_col]
    if has_sc:
        in_specs = [pl.BlockSpec(
            (s2, bn, bp), lambda *g: (0, canon(*g)[0], canon(*g)[1]))
        ] + in_specs
        operands = [shortcut] + operands
    scratch = {"output_stationary": [pltpu.VMEM((fa, bn, bp),
                                                jnp.float32)] * 2,
               "weight_stationary": [],
               "input_stationary": [pltpu.VMEM((fa, bm, bp),
                                               jnp.float32)] * 2}[flow]
    scratch = scratch + [pltpu.VMEM((DMA_SLOTS, s2, bn, bp), jnp.float32),
                         pltpu.SemaphoreType.DMA((DMA_SLOTS,))]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((s2, np_, pp_), jnp.float32),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=TPU_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# Host-side layouts of the two input modes
# ---------------------------------------------------------------------------

def _windows_layout(x: Array, geo: SpectralGeometry) -> tuple[Array, int]:
    """Overlap-save window extraction + s-leading layout [S, M, B*T]."""
    b, m = x.shape[:2]
    windows = extract_tiles_overlapping(x, geo)         # [B, M, T, K, K]
    t_cnt = windows.shape[2]
    s = geo.fft_size * geo.fft_size
    xt = (windows.reshape(b, m, t_cnt, s)
          .transpose(3, 1, 0, 2).reshape(s, m, b * t_cnt))
    return xt, t_cnt


def _shortcut_canvas(sc: Array, geo: SpectralGeometry, rows: int,
                     cols: int) -> Array:
    """RAW [B, N, H_out, W_out] shortcut embedded at its full-conv canvas
    position in a zero [B, N, rows, cols] canvas (positions outside the
    'same' crop are cropped from the output anyway)."""
    b, n, h, w = sc.shape
    start = geo.ksize - 1 - geo.pad
    canvas = jnp.zeros((b, n, rows, cols), jnp.float32)
    return canvas.at[:, :, start:start + h,
                     start:start + w].set(sc.astype(jnp.float32))


def _windowed_shortcut(sc: Array, geo: SpectralGeometry, t_cnt: int
                       ) -> Array:
    """Shortcut in the windowed output layout [S2, N, B*T] (inverse of
    ``assemble_tile_canvas``): t x t tiles, u-major rows."""
    b, n = sc.shape[:2]
    t = geo.tile
    canvas = _shortcut_canvas(sc, geo, geo.n_tiles_h * t,
                              geo.n_tiles_w * t)
    tiles = (canvas.reshape(b, n, geo.n_tiles_h, t, geo.n_tiles_w, t)
             .transpose(0, 1, 2, 4, 3, 5)        # [b, n, ith, jtw, u, v]
             .reshape(b, n, t_cnt, t * t))
    return tiles.transpose(3, 1, 0, 2).reshape(t * t, n, b * t_cnt)


def _windowed_canvas(y: Array, geo: SpectralGeometry, b: int, n: int,
                     t_cnt: int) -> Array:
    """[t^2, N, B*T] tile stream -> UNCROPPED [B, N, h_pad, w_pad]."""
    s2 = geo.tile * geo.tile
    y_tiles = (y.reshape(s2, n, b, t_cnt).transpose(2, 1, 3, 0)
               .reshape(b, n, t_cnt, geo.tile, geo.tile))
    return assemble_tile_canvas(y_tiles, geo)


def _halo_lanes(hg: HaloGeometry) -> int:
    """Lanes per tile row of a halo block: btw padded to a lane tile."""
    return lane_round(hg.btw)


def _halo_selectors(geo: SpectralGeometry, hg: HaloGeometry
                    ) -> tuple[np.ndarray, np.ndarray]:
    """(gr [nbh, bth*K, rh, 1], gc [nbw, rw, K*lw]): the selectors of
    ``halo_gather_matrices`` laid out for the kernel — each row-selector
    row as a column (the kernel masks the raw block with it), and
    output lane ``kw*lw + jj`` of the column selector picks
    window column kw of tile column jj; lanes jj >= btw stay zero (lane
    padding)."""
    k, lw = geo.fft_size, _halo_lanes(hg)
    gr, gc = halo_gather_matrices(geo, hg)
    g = gc.reshape(hg.nbw, hg.btw, k, hg.rw).transpose(0, 3, 2, 1)
    g = np.pad(g, ((0, 0), (0, 0), (0, 0), (0, lw - hg.btw)))
    return (np.ascontiguousarray(gr[..., None]),
            np.ascontiguousarray(g.reshape(hg.nbw, hg.rw, k * lw)))


def _halo_x_specs(geo: SpectralGeometry, hg: HaloGeometry, bm: int,
                  canon):
    """BlockSpecs of the halo input path.  The raw x block is addressed
    by element offsets (``pl.Element`` dims): the p grid axis enumerates
    (image, block-row, block-col) and the offsets are the traced twin of
    ``spectral.halo_block_starts`` — neighbouring blocks overlap by the
    k-1 halo, clamped at the image edges.  An axis with a single block
    reads from offset 0; row offsets are multiples of ``ROW_ALIGN``.
    The selectors use blocked indexing on their leading block axis."""
    t, ov = geo.tile, geo.ksize - 1
    nb = hg.n_blocks
    h_hi, w_hi = geo.h_in - hg.rh, geo.w_in - hg.rw
    k, lw = geo.fft_size, _halo_lanes(hg)

    def row(ib):
        if hg.nbh == 1:
            return 0
        # + pre_halo_h: sharded bands carry their top halo in-buffer.
        s = jnp.maximum(ib * hg.bth * t - ov + geo.pre_halo_h, 0)
        s = jnp.minimum(s // ROW_ALIGN * ROW_ALIGN, h_hi)
        return pl.multiple_of(s, ROW_ALIGN) if h_hi % ROW_ALIGN == 0 else s

    def col(jb):
        if hg.nbw == 1:
            return 0
        return jnp.clip(jb * hg.btw * t - ov, 0, w_hi)

    def x_idx(*g):
        _, p, m = canon(*g)
        b, ib, jb = p // nb, (p % nb) // hg.nbw, p % hg.nbw
        return (b, m * bm, row(ib), col(jb))

    x_spec = pl.BlockSpec((pl.Element(1), pl.Element(bm),
                           pl.Element(hg.rh), pl.Element(hg.rw)), x_idx)
    gr_spec = pl.BlockSpec(
        (1, hg.bth * k, hg.rh, 1),
        lambda *g: ((canon(*g)[1] % nb) // hg.nbw, 0, 0, 0))
    gc_spec = pl.BlockSpec(
        (1, hg.rw, k * lw), lambda *g: (canon(*g)[1] % hg.nbw, 0, 0))
    return x_spec, gr_spec, gc_spec


def _halo_shortcut(sc: Array, geo: SpectralGeometry, hg: HaloGeometry
                   ) -> Array:
    """Shortcut in the halo output layout [S2, N, B*nb*bth*lw]: lanes
    (image, block-row, block-col, tile-row ii, tile-col jj padded to
    lw) — the inverse of ``_halo_canvas``."""
    b, n = sc.shape[:2]
    t, lw = geo.tile, _halo_lanes(hg)
    canvas = _shortcut_canvas(sc, geo, hg.nbh * hg.bth * t,
                              hg.nbw * hg.btw * t)
    y = canvas.reshape(b, n, hg.nbh, hg.bth, t, hg.nbw, hg.btw, t)
    y = y.transpose(4, 7, 1, 0, 2, 5, 3, 6)      # u v n b ib jb ii jj
    y = jnp.pad(y, [(0, 0)] * 7 + [(0, lw - hg.btw)])
    return y.reshape(t * t, n, b * hg.n_blocks * hg.bth * lw)


def _halo_canvas(y: Array, geo: SpectralGeometry, hg: HaloGeometry,
                 b: int, n: int) -> Array:
    """Halo tile stream [S2, >=N, B*nb*bth*lw] -> UNCROPPED [B, N,
    h_pad, w_pad]: tile (ib*bth + ii, jb*btw + jj) lands at canvas
    (i*t, j*t); lane padding and tiles past the grid are dropped."""
    t, lw = geo.tile, _halo_lanes(hg)
    y = y[:, :n].reshape(t, t, n, b, hg.nbh, hg.nbw, hg.bth, lw)
    y = y[..., :hg.btw].transpose(3, 2, 4, 6, 0, 5, 7, 1)
    y = y.reshape(b, n, hg.nbh * hg.bth * t, hg.nbw * hg.btw * t)
    return y[:, :, :geo.h_pad, :geo.w_pad]


# ---------------------------------------------------------------------------
# The jitted conv: layout -> pallas_call -> canvas
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("geo", "input_mode", "flow", "block_n", "block_m",
                     "block_p", "relu", "interpret", "crop"))
def _fused_conv(x: Array, w_ops: tuple, dfr: Array, dfi: Array,
                dvr: Array, dvi: Array, bias: Array,
                shortcut: Array | None = None, *,
                geo: SpectralGeometry, input_mode: str, flow: str,
                block_n: int, block_m: int, block_p: int, relu: bool,
                interpret: bool, crop: bool = True) -> Array:
    """One fused spectral conv: host layout, the pallas_call, and the
    tile-stream -> canvas relayout (+ 'same' crop when ``crop``; sharded
    bands keep the uncropped canvas).

    x: [B, M, H, W] raw NCHW activation.
    w_ops: ``(wr, wi)`` planes [Fa, N, M] ('dense'/'bin'), or the Alg-2
        tables ``(idx, sel, vr, vi)`` — [GN, Mp, T, r] / [GN, Mp, T, N']
        compiled with ``m_pad_to == min(block_m, M)``; block_n is then
        the schedule's group size N'.
    dfr/dfi [Fa, K^2], dvr/dvi [t^2, Fa]: ``overlap_save_operators``.
    bias: [1, N].  shortcut: optional RAW [B, N, H_out, W_out] residual
        operand, relaid to the output layout and added in-kernel after
        bias, before ReLU.
    """
    if flow not in FLOWS:
        raise ValueError(f"flow must be one of {FLOWS}")
    if input_mode not in INPUT_MODES:
        raise ValueError(f"input_mode must be one of {INPUT_MODES}")
    b, m = x.shape[:2]
    n = bias.shape[1]
    fa = dfr.shape[0]
    k = geo.fft_size
    tables = len(w_ops) == 4
    bm = min(block_m, m)
    if bm == m:                      # full-extent M: pad to a sublane tile
        bm = -(-m // SUBLANES) * SUBLANES
    x = _pad_axis(x.astype(jnp.float32), 1, bm)
    mp_ = x.shape[1]
    if tables:
        if w_ops[0].shape[1] < mp_:  # inert zero channels, as compiled
            w_ops = tuple(_pad_axis(w, 1, mp_) for w in w_ops)
        gn, mp_t, _, _ = w_ops[0].shape
        bn = w_ops[1].shape[3]
        assert mp_t == mp_, (
            f"tables padded for {mp_t} channels but the input pads to "
            f"{mp_}; compile_layer_tables(m_pad_to=block_m) must use the "
            f"same block_m (= {bm})")
        np_ = gn * bn
        w_src = functools.partial(_TableBlock, fa=fa)
        t_cycles, r = w_ops[0].shape[2:]
        t_spec = lambda lanes: pl.BlockSpec(
            (1, bm, t_cycles, lanes),
            lambda *g: (canon(*g)[0], canon(*g)[2], 0, 0))
        w_specs = [t_spec(r)] + [t_spec(bn)] * 3
    else:
        bn = min(block_n, n)
        w_ops = tuple(_pad_axis(_pad_axis(w, 1, bn), 2, bm) for w in w_ops)
        np_ = w_ops[0].shape[1]
        gn = np_ // bn
        w_src = _PlaneBlock
        w_spec = pl.BlockSpec(
            (fa, bn, bm), lambda *g: (0, canon(*g)[0], canon(*g)[2]))
        w_specs = [w_spec, w_spec]
    gm = mp_ // bm
    bias_col = _pad_axis(bias.astype(jnp.float32), 1, bn).reshape(np_, 1)
    ops = (dfr, dfi, dvr, dvi)

    if input_mode == "halo":
        hg = halo_block_geometry(geo, block_p)
        lw = _halo_lanes(hg)
        bp = hg.bth * lw
        gp = b * hg.n_blocks
        canon_layout = _flow_layout(flow, gn, gm, gp)
        canon = canon_layout[1]
        gr, gc = _halo_selectors(geo, hg)
        x_ops = (x, jnp.asarray(gr), jnp.asarray(gc))
        x_specs = _halo_x_specs(geo, hg, bm, canon)
        x_src = functools.partial(_HaloBlock, fft_size=k, bth=hg.bth,
                                  lw=lw)
        sc = None if shortcut is None else _halo_shortcut(shortcut, geo, hg)
        pp_ = gp * bp
    else:
        xt, t_cnt = _windows_layout(x, geo)             # [S, Mp, B*T]
        bp = lane_round(min(block_p, b * t_cnt))
        xt = _pad_axis(xt, 2, bp)
        pp_ = xt.shape[2]
        gp = pp_ // bp
        canon_layout = _flow_layout(flow, gn, gm, gp)
        canon = canon_layout[1]
        x_ops = (xt,)
        x_specs = [pl.BlockSpec(
            (k * k, bm, bp), lambda *g: (0, canon(*g)[2], canon(*g)[1]))]
        x_src = _WindowBlock
        sc = (None if shortcut is None
              else _windowed_shortcut(shortcut, geo, t_cnt))
    if sc is not None:
        sc = _pad_axis(_pad_axis(sc, 1, bn), 2, bp)

    y = _fused_call(x_src, x_ops, x_specs, w_src, w_ops, w_specs, ops,
                    bias_col, sc, flow=flow, grid_counts=(gn, gm, gp),
                    canon_layout=canon_layout, bn=bn, bm=bm, bp=bp,
                    np_=np_, pp_=pp_, relu=relu, interpret=interpret)
    if input_mode == "halo":
        canvas = _halo_canvas(y, geo, hg, b, n)
    else:
        canvas = _windowed_canvas(y[:, :n, :b * t_cnt], geo, b, n, t_cnt)
    return crop_canvas_same(canvas, geo) if crop else canvas


def _bias_row(bias, n: int) -> Array:
    if bias is None:
        return jnp.zeros((1, n), jnp.float32)
    return jnp.asarray(bias, jnp.float32).reshape(1, n)


def fused_spectral_conv2d(x: Array, w_f, geo: SpectralGeometry, *,
                          flow: str = "output_stationary",
                          block_n: int = 64, block_m: int = 64,
                          block_p: int = 128, bias: Array | None = None,
                          relu: bool = False,
                          input_mode: str = "windowed",
                          interpret: bool | None = None) -> Array:
    """Full spectral conv layer through the single fused pallas_call.

    x: [B, M, H, W] real NCHW; w_f: complex [N, M, K, K] dense, or a
    ``SparseSpectralKernels`` whose active-bin set drives the spectral
    GEMM compaction (dense fallback when nnz ~= K^2).  ``bias``/``relu``
    select the fused epilogue.  ``input_mode`` selects the input path
    (``dataflow.INPUT_MODES``): 'windowed' materializes the overlap-save
    window tensor host-side, 'halo' reads the raw activation through
    overlapping halo blocks and gathers the windows in VMEM.

    NOTE: this ad-hoc entry recomputes compaction + DFT operators per
    call; the compile-once path is ``core.plan.build_network_plan`` +
    ``execute_layer_plan``.
    """
    if input_mode not in INPUT_MODES:
        raise ValueError(f"input_mode must be one of {INPUT_MODES}, "
                         f"got {input_mode!r}")
    if interpret is None:
        interpret = default_interpret()
    if hasattr(w_f, "values"):            # SparseSpectralKernels duck-type
        active = sp.compacted_active_bins(w_f)
        wr, wi = sp.compact_planes(w_f, active)
        n = w_f.n_out
        assert w_f.fft_size == geo.fft_size
    else:
        assert w_f.shape[-1] == geo.fft_size
        active = None
        n, m = w_f.shape[:2]
        flat = w_f.reshape(n, m, geo.fft_size * geo.fft_size)
        wr = jnp.transpose(flat.real, (2, 0, 1)).astype(jnp.float32)
        wi = jnp.transpose(flat.imag, (2, 0, 1)).astype(jnp.float32)
    ops = overlap_save_operators(
        geo.fft_size, geo.ksize,
        tuple(int(a) for a in active) if active is not None else None)
    y = _fused_conv(x, (wr, wi), *(jnp.asarray(a) for a in ops),
                    _bias_row(bias, n), geo=geo, input_mode=input_mode,
                    flow=flow, block_n=block_n, block_m=block_m,
                    block_p=block_p, relu=relu, interpret=interpret)
    return y.astype(x.dtype)


def fused_spectral_conv2d_scheduled(x: Array, sk, geo: SpectralGeometry,
                                    *, r: int = 10, n_par: int = 64,
                                    flow: str = "output_stationary",
                                    block_m: int = 64, block_p: int = 128,
                                    bias: Array | None = None,
                                    relu: bool = False,
                                    method: str = "exact_cover",
                                    tables=None,
                                    input_mode: str = "windowed",
                                    interpret: bool | None = None
                                    ) -> Array:
    """Full spectral conv layer through the SCHEDULED fused pallas_call.

    x: [B, M, H, W] real NCHW; sk: ``SparseSpectralKernels`` whose Alg-2
    exact-cover schedule (group size ``n_par`` == the kernel's block_n,
    ``r`` BRAM-replica analogue) is compiled to INDEX/VALUE tables here
    and executed inside the fused kernel.  Pass a precompiled
    ``scheduler.LayerTables`` via ``tables`` to skip the per-call
    scheduling (built with the same ``active`` set and ``m_pad_to ==
    min(block_m, M)``).

    NOTE: without ``tables`` this ad-hoc entry runs the scheduler per
    call; the compile-once path is ``core.plan.build_network_plan(
    hadamard='scheduled'|'auto')`` + ``execute_layer_plan``.
    """
    from repro.core import scheduler as sch

    if input_mode not in INPUT_MODES:
        raise ValueError(f"input_mode must be one of {INPUT_MODES}, "
                         f"got {input_mode!r}")
    if interpret is None:
        interpret = default_interpret()
    assert sk.fft_size == geo.fft_size
    k2 = geo.fft_size * geo.fft_size
    n, m = sk.n_out, sk.n_in
    bm = min(block_m, m)
    n_par = min(n_par, n)
    active = sp.compacted_active_bins(sk)
    tabs = tables
    if tabs is None:
        vals = np.asarray(sk.values).reshape(n, m, k2)
        tabs = sch.compile_layer_tables(
            np.asarray(sk.indices), vals, k2, r, n_par,
            method=method, active=active, m_pad_to=bm)
    ops = overlap_save_operators(
        geo.fft_size, geo.ksize,
        tuple(int(a) for a in active) if active is not None else None)
    w_ops = tuple(jnp.asarray(a) for a in (tabs.idx, tabs.sel, tabs.vr,
                                           tabs.vi))
    y = _fused_conv(x, w_ops, *(jnp.asarray(a) for a in ops),
                    _bias_row(bias, n), geo=geo, input_mode=input_mode,
                    flow=flow, block_n=n_par, block_m=bm,
                    block_p=block_p, relu=relu, interpret=interpret)
    return y.astype(x.dtype)


def _run_plan(x: Array, lp, shortcut, *, interpret: bool | None,
              crop: bool) -> Array:
    """Dispatch one ``LayerPlan`` onto ``_fused_conv``.  The
    fault-injection sites live HERE — outside the jitted pipeline — so
    a warm jit cache can never bypass them; this is also where a real
    Mosaic lowering failure or VMEM exhaustion surfaces on hardware."""
    if interpret is None:
        interpret = default_interpret()
    tn = lp.tuning
    ctx = dict(layer=lp.layer.name, backend="fused", flow=tn.flow,
               hadamard=getattr(lp, "hadamard", None),
               input_mode=getattr(lp, "input_mode", "windowed"),
               residual=getattr(lp.epilogue, "residual", None))
    res.fault_check("lowering", **ctx)
    res.fault_check("vmem_overflow", **ctx)
    bias = (lp.bias if lp.epilogue.bias
            else jnp.zeros(lp.bias.shape, lp.bias.dtype))
    if getattr(lp, "hadamard", None) == "scheduled":
        tb = lp.tables
        w_ops, block_n = (tb.idx, tb.sel, tb.vr, tb.vi), tb.sel.shape[3]
    else:
        w_ops, block_n = (lp.wr, lp.wi), tn.block_n
    y = _fused_conv(x, w_ops, lp.dfr, lp.dfi, lp.dvr, lp.dvi, bias,
                    shortcut, geo=lp.geo,
                    input_mode=getattr(lp, "input_mode", "windowed"),
                    flow=tn.flow, block_n=block_n, block_m=tn.block_m,
                    block_p=tn.block_p, relu=lp.epilogue.relu,
                    interpret=interpret, crop=crop)
    return res.fault_corrupt("nan_activations", y.astype(x.dtype), **ctx)


def execute_layer_plan(x: Array, lp, *, interpret: bool | None = None,
                       shortcut: Array | None = None) -> Array:
    """Run one conv layer from a precompiled ``core.plan.LayerPlan``.

    Consumes the plan's precomputed operands and dispatches on its
    Hadamard mode ('dense'/'bin' planes or 'scheduled' tables) and input
    mode; nothing is re-derived per call, so repeated forwards hit the
    jit cache of ``_fused_conv``.  Pooling (``lp.epilogue.pool``) stays
    with the caller.

    ``shortcut``: RAW [B, N, H_out, W_out] residual operand for plans
    whose epilogue is residual-FUSED (``lp.epilogue.residual ==
    'fused'``); the DAG executor passes the producer node's activation
    and the kernel adds it after bias, before ReLU.
    """
    return _run_plan(x, lp, shortcut, interpret=interpret, crop=True)


def execute_band_plan(x_ext: Array, lp, *, interpret: bool | None = None
                      ) -> Array:
    """Run one conv layer's SHARD-LOCAL band from a per-shard
    ``core.plan.LayerPlan`` whose geometry is a ``make_band_geometry``
    result (pre_halo_h = k-1).

    ``x_ext`` is the extended band [B, M, (k-1) + tr*t, W] — the shard's
    raw rows prefixed by the halo rows its mesh neighbour sent
    (``lax.ppermute`` inside the sharded executor; zeros on shard 0).
    Returns the UNCROPPED band canvas [B, N, tr*t, w_pad]: the 'same'
    crop is global, so it runs after the shards' canvases are
    concatenated (``spectral.crop_canvas_same``).
    """
    return _run_plan(x_ext, lp, None, interpret=interpret, crop=False)
