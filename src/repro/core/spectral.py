"""Spectral (FFT-domain) convolution with tiling and Overlap-and-Add.

This is the mathematical substrate of the paper (§3, Eqs 3-4): spatial
convolution is replaced by

    1. tile the input into h' x w' tiles,
    2. zero-pad each tile to K x K  (K = h' + k - 1)  and 2-D FFT it,
    3. Hadamard-multiply with the K x K spectral kernels and accumulate
       over input channels  (Eq 3),
    4. inverse FFT each output tile,
    5. Overlap-and-Add (OaA) the output tiles (adjacent tiles overlap by
       k - 1 pixels)  (Eq 4).

Everything here is pure JAX and serves both as the production forward path
on CPU/TPU and as the oracle for the Pallas kernels in ``repro.kernels``.

Two tilings are provided.  The paper's OaA decomposition
(``extract_tiles`` / ``overlap_add``) sums overlapping K x K output
tiles, so per-tile outputs are *partial* until OaA completes.  The
production forward path instead uses the dual overlap-save decomposition
(``extract_tiles_overlapping`` / ``assemble_valid_tiles``): overlapping
K x K *input* windows whose t x t valid outputs are complete — which is
what lets the fused Pallas kernel apply bias + ReLU inside its flush
step (DESIGN.md adaptation note 5).  For un-pruned kernels the two are
numerically identical (both equal ``spatial_conv2d``).

Conventions
-----------
* CNN "convolution" is cross-correlation; we FLIP the spatial kernel before
  the FFT so that the spectral Hadamard product implements correlation.
* Activations are NCHW: ``x[b, c, h, w]``; kernels ``w[n, m, k, k]``
  (out-channels, in-channels, kh, kw) — the paper's notation.
* Only stride-1 convolutions are tiled spectrally (VGG16 uses stride 1
  everywhere in its conv stack); pooling happens in the spatial domain.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


class SpectralGeometry(NamedTuple):
    """Static geometry of a tiled spectral convolution."""

    fft_size: int        # K
    tile: int            # h' = w' = K - k + 1
    ksize: int           # spatial kernel size k
    pad: int             # spatial 'same' padding p (VGG16: 1)
    h_in: int            # input spatial height (pre-padding)
    w_in: int
    n_tiles_h: int       # tiles along H after padding to a multiple of h'
    n_tiles_w: int
    h_pad: int           # padded input size = n_tiles_h * tile
    w_pad: int
    # Rows of TOP halo already PRESENT in the input (sharded bands):
    # the first pre_halo_h input rows are a neighbour shard's bottom
    # rows (or explicit zeros on shard 0), so overlap-save extraction
    # zero-pads only the remaining k-1-pre_halo_h halo rows and every
    # H-axis window/gather coordinate shifts down by pre_halo_h.
    # 0 (the default, and the only value `make_geometry` emits) is the
    # single-device geometry — all formulas reduce to their PR-5 form.
    pre_halo_h: int = 0

    @property
    def n_tiles(self) -> int:
        return self.n_tiles_h * self.n_tiles_w


def make_geometry(h_in: int, w_in: int, ksize: int, fft_size: int,
                  pad: int | None = None) -> SpectralGeometry:
    tile = fft_size - ksize + 1
    if tile <= 0:
        raise ValueError(f"fft_size {fft_size} too small for kernel {ksize}")
    if ksize - 1 > tile:
        raise ValueError("OaA decomposition requires k - 1 <= tile size")
    if pad is None:
        pad = (ksize - 1) // 2  # 'same' for odd kernels
    # Tile the input padded by at least `pad` on the bottom/right so the
    # cropped 'same' output never reads past the tiled canvas.
    n_th = -(-(h_in + pad) // tile)
    n_tw = -(-(w_in + pad) // tile)
    return SpectralGeometry(fft_size, tile, ksize, pad, h_in, w_in,
                            n_th, n_tw, n_th * tile, n_tw * tile)


# ---------------------------------------------------------------------------
# Kernel transform
# ---------------------------------------------------------------------------

def spectral_kernel(w: Array, fft_size: int) -> Array:
    """Spatial kernel [N, M, k, k] -> spectral kernel [N, M, K, K] complex.

    The kernel is flipped (correlation -> convolution) and zero-padded to
    K x K before the FFT.  This is done once, offline, exactly as the paper
    stores pre-transformed spectral kernels in DDR.
    """
    k = w.shape[-1]
    w = w[..., ::-1, ::-1]
    w = jnp.pad(w, [(0, 0)] * (w.ndim - 2) + [(0, fft_size - k)] * 2)
    return jnp.fft.fft2(w.astype(jnp.float32))


# ---------------------------------------------------------------------------
# Input tiling / output OaA
# ---------------------------------------------------------------------------

def extract_tiles(x: Array, geo: SpectralGeometry) -> Array:
    """[B, M, H, W] -> [B, M, T, h', w']  (T = n_tiles, row-major)."""
    b, m = x.shape[:2]
    x = jnp.pad(x, ((0, 0), (0, 0),
                    (0, geo.h_pad - geo.h_in), (0, geo.w_pad - geo.w_in)))
    x = x.reshape(b, m, geo.n_tiles_h, geo.tile, geo.n_tiles_w, geo.tile)
    x = x.transpose(0, 1, 2, 4, 3, 5)
    return x.reshape(b, m, geo.n_tiles, geo.tile, geo.tile)


def extract_tiles_overlapping(x: Array, geo: SpectralGeometry) -> Array:
    """[B, M, H, W] -> [B, M, T, K, K] overlap-save input tiles.

    Overlap-save (a.k.a. overlap-discard) is the dual of OaA: instead of
    disjoint h' x h' tiles whose K x K full-conv outputs are summed, take
    *overlapping* K x K input windows with stride h' starting at offset
    -(k-1).  The K-point circular convolution of such a window with the
    (flipped, K-padded) kernel is wraparound-free at output rows k-1..K-1,
    and those t x t = h' x h' valid outputs are exactly the full-conv
    canvas block at (i*h', j*h') — **complete**, with no cross-tile
    additions pending.  That is what lets the fused kernel apply a
    non-linear epilogue (bias + ReLU) inside its flush step: every value
    it writes is a finished pre-activation.  The price is re-reading the
    k-1-pixel halo between neighbouring windows ((K/h')^2 input traffic
    instead of 1x) — the same duplicated-halo DMA the paper's FPGA input
    loader performs.
    """
    b, m = x.shape[:2]
    ov = geo.ksize - 1
    x = jnp.pad(x, ((0, 0), (0, 0),
                    (ov - geo.pre_halo_h,
                     geo.h_pad + geo.pre_halo_h - geo.h_in),
                    (ov, geo.w_pad - geo.w_in)))
    ih = (np.arange(geo.n_tiles_h)[:, None] * geo.tile
          + np.arange(geo.fft_size)[None, :])           # [n_th, K]
    iw = (np.arange(geo.n_tiles_w)[:, None] * geo.tile
          + np.arange(geo.fft_size)[None, :])           # [n_tw, K]
    xt = x[:, :, ih][:, :, :, :, iw]                    # [B,M,n_th,K,n_tw,K]
    xt = xt.transpose(0, 1, 2, 4, 3, 5)
    return xt.reshape(b, m, geo.n_tiles, geo.fft_size, geo.fft_size)


class HaloGeometry(NamedTuple):
    """Static geometry of the in-kernel halo gather (PR 5 tentpole).

    The fused kernel's halo input mode reads the RAW NCHW activation
    directly: each grid step gets an input block covering ``bth x btw``
    tiles *plus* the k-1-pixel halo the overlap-save windows share —
    ``rh = bth*t + (K - t)`` rows by ``rw = btw*t + (K - t)`` cols,
    clamped to the image (small images fit in one block) — and gathers
    its stride-t, size-K windows in VMEM with one-hot row/col matmuls
    (``halo_gather_matrices``).  Consecutive blocks overlap by the halo,
    which Pallas expresses with element-offset (``pl.Element``) block
    dims; no ``[B, M, T, K, K]`` windowed tensor is ever materialized
    in HBM.  Rows are the TPU's sublane axis, so a block split along H
    starts on a multiple of ``ROW_ALIGN`` rows and reads that many
    extra rows (``halo_block_geometry``).
    """

    bth: int             # tiles per block along H
    btw: int             # tiles per block along W
    nbh: int             # blocks along H  (ceil(n_tiles_h / bth))
    nbw: int             # blocks along W
    rh: int              # raw rows per block (aligned, <= h_in)
    rw: int              # raw cols per block: min(btw*t + k - 1, w_in)

    @property
    def block_tiles(self) -> int:
        """Tiles per grid step — the halo path's effective block_p."""
        return self.bth * self.btw

    @property
    def n_blocks(self) -> int:
        return self.nbh * self.nbw


def halo_block_geometry(geo: SpectralGeometry, block_p: int) -> HaloGeometry:
    """Split a tile-count budget ``block_p`` into a 2-D halo block.

    Favors full tile rows (btw first) so the per-axis halo fraction
    (K - t)/(b*t) is paid on as few axes as possible; the resulting
    ``block_tiles = bth*btw <= block_p`` is what the VMEM/psum blocks
    are sized by.  Deterministic: the kernel, the cost model and the
    autotuner all derive the same blocks from (geo, block_p).

    When the image splits into several block rows (nbh > 1), each
    block's read starts at its first window row rounded DOWN to a
    multiple of ``ROW_ALIGN`` (the TPU's sublane tile), so ``rh`` grows
    by the largest such rounding and is itself rounded up to
    ``ROW_ALIGN``.  A single block row reads the whole height.
    """
    block_p = max(1, block_p)
    btw = max(1, min(geo.n_tiles_w, block_p))
    bth = max(1, min(geo.n_tiles_h, block_p // btw))
    ov = geo.ksize - 1
    nbh = -(-geo.n_tiles_h // bth)
    need = bth * geo.tile + ov
    if nbh > 1:
        slack = int((_raw_row_starts(geo, bth, nbh) % ROW_ALIGN).max())
        need = -(-(need + slack) // ROW_ALIGN) * ROW_ALIGN
    return HaloGeometry(
        bth=bth, btw=btw, nbh=nbh, nbw=-(-geo.n_tiles_w // btw),
        rh=min(need, geo.h_in),
        rw=min(btw * geo.tile + ov, geo.w_in))


# Row alignment of a halo block's start and height: the f32 sublane
# tile of a TPU vreg.  Mosaic DMAs a block only from a second-minor
# offset it can prove to be a multiple of this.
ROW_ALIGN = 8


def _raw_row_starts(geo: SpectralGeometry, bth: int, nbh: int
                    ) -> np.ndarray:
    """First raw row each block row's windows read (>= 0)."""
    ov = geo.ksize - 1
    return np.maximum(np.arange(nbh) * bth * geo.tile - ov
                      + geo.pre_halo_h, 0)


def halo_block_starts(geo: SpectralGeometry, hg: HaloGeometry
                      ) -> tuple[np.ndarray, np.ndarray]:
    """Clamped raw-image start offsets of every halo block, per axis.

    Block ib's windows span raw rows from ``ib*bth*t - (k-1)``; its read
    starts there rounded down to ``ROW_ALIGN`` and clamped to ``[0,
    h_in - rh]`` so the block never reads out of bounds — the gather
    matrices re-align the windows against the block's start and encode
    the 'same'-padding (and bottom/right tile padding) as all-zero
    one-hot rows.  The kernel's element-offset index map computes
    exactly this formula on traced indices.
    """
    ov = geo.ksize - 1
    sh = _raw_row_starts(geo, hg.bth, hg.nbh) // ROW_ALIGN * ROW_ALIGN
    sw = np.arange(hg.nbw) * hg.btw * geo.tile - ov
    return (np.minimum(sh, geo.h_in - hg.rh),
            np.clip(sw, 0, geo.w_in - hg.rw))


def halo_gather_matrices(geo: SpectralGeometry, hg: HaloGeometry
                         ) -> tuple[np.ndarray, np.ndarray]:
    """One-hot window selectors for the in-kernel halo gather.

    gr [nbh, bth*K, rh] / gc [nbw, btw*K, rw] f32: row ``ii*K + kh`` of
    block ib selects raw image row ``(ib*bth + ii)*t - (k-1) + kh``
    relative to the block's clamped start.  Rows whose raw coordinate
    falls outside the image ('same' zero-padding, bottom/right tile
    padding past n_tiles) or whose tile index exceeds the tile grid are
    left all-zero, so the gathered window values are exact zeros — the
    one-hot matmul IS the zero-padding.  Being 0/1 operands, the gather
    is numerically exact: halo windows equal
    ``extract_tiles_overlapping`` bit for bit.
    """
    k = geo.fft_size
    ov = geo.ksize - 1
    sh, sw = halo_block_starts(geo, hg)

    def axis(nb, bt, n_tiles, start, size, extent, pre=0):
        g = np.zeros((nb, bt * k, size), np.float32)
        for ib in range(nb):
            for ii in range(bt):
                tile_idx = ib * bt + ii
                if tile_idx >= n_tiles:
                    continue                      # block padding tile
                for kh in range(k):
                    raw = tile_idx * geo.tile - ov + kh + pre
                    if 0 <= raw < extent:
                        g[ib, ii * k + kh, raw - start[ib]] = 1.0
        return g

    return (axis(hg.nbh, hg.bth, geo.n_tiles_h, sh, hg.rh, geo.h_in,
                 geo.pre_halo_h),
            axis(hg.nbw, hg.btw, geo.n_tiles_w, sw, hg.rw, geo.w_in))


def halo_window_reference(x: Array, geo: SpectralGeometry,
                          hg: HaloGeometry) -> Array:
    """Host-side emulation of the kernel's halo gather (tests/docs).

    Replays exactly what the fused kernel does per grid step — clamped
    raw block read, one-hot row/col gather — then reorders the
    block-major tiles back to row-major and crops the block padding.
    Must equal ``extract_tiles_overlapping(x, geo)`` for every
    (H, W, k, t, block_p) the plan can emit (property-tested).
    """
    b, m = x.shape[:2]
    k = geo.fft_size
    gr, gc = halo_gather_matrices(geo, hg)
    sh, sw = halo_block_starts(geo, hg)
    xn = np.asarray(x)
    out = np.zeros((b, m, hg.nbh * hg.bth, hg.nbw * hg.btw, k, k),
                   xn.dtype)
    for ib in range(hg.nbh):
        for jb in range(hg.nbw):
            blk = xn[:, :, sh[ib]:sh[ib] + hg.rh, sw[jb]:sw[jb] + hg.rw]
            win = np.einsum("rh,bmhw,cw->bmrc", gr[ib], blk, gc[jb])
            win = win.reshape(b, m, hg.bth, k, hg.btw, k)
            out[:, :, ib * hg.bth:(ib + 1) * hg.bth,
                jb * hg.btw:(jb + 1) * hg.btw] = win.transpose(
                    0, 1, 2, 4, 3, 5)
    out = out[:, :, :geo.n_tiles_h, :geo.n_tiles_w]
    return jnp.asarray(out.reshape(b, m, geo.n_tiles, k, k))


def assemble_tile_canvas(y_tiles: Array, geo: SpectralGeometry) -> Array:
    """[B, N, T, h', h'] valid tiles -> UNCROPPED [B, N, h_pad, w_pad]
    full-conv canvas (pure relayout, no overlap additions).

    The sharded executor assembles each shard's band canvas with this
    and crops only after concatenating the bands — the 'same' crop is a
    global operation (its start offset is relative to the whole image),
    so per-shard outputs must stay uncropped.
    """
    b, n, t, tl, _ = y_tiles.shape
    assert t == geo.n_tiles and tl == geo.tile
    yt = y_tiles.reshape(b, n, geo.n_tiles_h, geo.n_tiles_w, tl, tl)
    return (yt.transpose(0, 1, 2, 4, 3, 5)
            .reshape(b, n, geo.h_pad, geo.w_pad))


def crop_canvas_same(canvas: Array, geo: SpectralGeometry) -> Array:
    """'same' crop of a full-conv canvas: [B, N, h_pad*, w_pad] ->
    [B, N, H_out, W_out].  ``geo`` must be the GLOBAL geometry (the
    canvas may be taller than h_pad when bands were concatenated past
    the image; only the cropped range is read)."""
    start = geo.ksize - 1 - geo.pad
    h_out = geo.h_in + 2 * geo.pad - geo.ksize + 1
    w_out = geo.w_in + 2 * geo.pad - geo.ksize + 1
    return canvas[:, :, start:start + h_out, start:start + w_out]


def assemble_valid_tiles(y_tiles: Array, geo: SpectralGeometry) -> Array:
    """Overlap-save output assembly: [B, N, T, h', h'] valid tiles ->
    [B, N, H_out, W_out].

    Each tile's t x t block is the finished full-conv canvas block at
    (i*h', j*h') (see ``extract_tiles_overlapping``), so assembly is a
    pure relayout — no overlap additions — followed by the same 'same'
    crop as ``overlap_add``.
    """
    return crop_canvas_same(assemble_tile_canvas(y_tiles, geo), geo)


# ---------------------------------------------------------------------------
# Spatial sharding: tile-row bands + cross-shard halo (ISSUE 9)
# ---------------------------------------------------------------------------
#
# Spatial sharding splits the image into horizontal BANDS of whole tile
# rows (pruned-kernel overlap-save semantics are tile-alignment
# dependent, so shard boundaries must fall on tile boundaries).  Shard d
# owns tile rows [d*tr, (d+1)*tr) = raw rows [d*tr*t, (d+1)*tr*t), and
# needs exactly ov = k-1 rows of TOP halo from shard d-1 (shard 0's
# halo is zeros — the global 'same' padding); no bottom halo, because a
# window starting at the band's last tile row spans (tr-1)*t + K =
# tr*t + ov rows, i.e. ends inside the band + its own top halo.  The
# extended band [B, C, ov + tr*t, W] is described by
# ``make_band_geometry`` — a SpectralGeometry with pre_halo_h = ov whose
# extraction needs ZERO H padding and whose gather coordinates are all
# in bounds by construction (property-tested).

def shard_band_rows(geo: SpectralGeometry, n_shards: int) -> int:
    """Tile rows per shard band: ceil(n_tiles_h / n_shards)."""
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    return -(-geo.n_tiles_h // n_shards)


def make_band_geometry(geo: SpectralGeometry,
                       tile_rows: int) -> SpectralGeometry:
    """Per-shard geometry of a ``tile_rows``-tall band of ``geo``.

    The band input is the EXTENDED band [B, C, (k-1) + tile_rows*t, W]
    (top halo included), so h_in counts the halo rows and pre_halo_h
    marks them; h_pad is the band's canvas height tile_rows*t.  W-axis
    geometry is inherited unchanged (bands span the full width).
    """
    ov = geo.ksize - 1
    return SpectralGeometry(
        geo.fft_size, geo.tile, geo.ksize, geo.pad,
        h_in=ov + tile_rows * geo.tile, w_in=geo.w_in,
        n_tiles_h=tile_rows, n_tiles_w=geo.n_tiles_w,
        h_pad=tile_rows * geo.tile, w_pad=geo.w_pad,
        pre_halo_h=ov)


def halo_exchange_reference(x: Array, geo: SpectralGeometry,
                            n_shards: int) -> list[Array]:
    """Host emulation of the cross-shard halo exchange (tests/docs).

    Returns the ``n_shards`` extended bands [B, C, (k-1) + tr*t, W] the
    ppermute exchange produces on-device: shard d's band of the
    (bottom-zero-padded) image prefixed by the last k-1 rows of shard
    d-1's band (zeros for shard 0).  Exactly k-1 rows cross each
    boundary — the property the geometry test pins down.
    """
    ov = geo.ksize - 1
    tr = shard_band_rows(geo, n_shards)
    hb = tr * geo.tile
    xn = np.asarray(x)
    b, c, h, w = xn.shape
    xp = np.zeros((b, c, n_shards * hb, w), xn.dtype)
    xp[:, :, :h] = xn
    bands = []
    for d in range(n_shards):
        halo = (np.zeros((b, c, ov, w), xn.dtype) if d == 0
                else xp[:, :, d * hb - ov:d * hb])
        bands.append(jnp.asarray(
            np.concatenate([halo, xp[:, :, d * hb:(d + 1) * hb]], axis=2)))
    return bands


def spectral_band_conv2d_pretransformed(x_ext: Array, w_f,
                                        geo: SpectralGeometry) -> Array:
    """Band einsum oracle: one shard's extended band -> its UNCROPPED
    band canvas [B, N, tile_rows*t, w_pad].

    ``geo`` is a ``make_band_geometry`` result; ``x_ext`` is the
    extended band (top halo included).  Concatenating the shard
    canvases along H reconstructs ``assemble_tile_canvas`` of the
    unsharded image: the band windows are BIT-identical to the
    full-image overlap-save windows (property-tested), and the canvas
    matches to float-accumulation tolerance — XLA may schedule the
    Hadamard contraction differently at band vs full tile extents.
    ``crop_canvas_same`` with the GLOBAL geometry then yields the
    'same' output.
    """
    windows = extract_tiles_overlapping(x_ext, geo)  # [B,M,T,K,K]
    x_f = jnp.fft.fft2(windows.astype(jnp.float32))
    y_f = _hadamard_maybe_sparse(x_f, w_f, geo)
    y_sp = jnp.fft.ifft2(y_f).real
    ov = geo.ksize - 1
    y_valid = y_sp[..., ov:, ov:]
    return assemble_tile_canvas(y_valid.astype(x_ext.dtype), geo)


def fft_tiles(tiles: Array, geo: SpectralGeometry) -> Array:
    """[..., h', w'] -> [..., K, K] complex spectral tiles."""
    pad = geo.fft_size - geo.tile
    tiles = jnp.pad(tiles, [(0, 0)] * (tiles.ndim - 2) + [(0, pad)] * 2)
    return jnp.fft.fft2(tiles.astype(jnp.float32))


def overlap_add(y_tiles: Array, geo: SpectralGeometry) -> Array:
    """OaA merge: [B, N, T, K, K] spatial-domain output tiles -> [B, N, H, W].

    Tile (i, j)'s K x K full-convolution output sits at canvas offset
    (i*tile, j*tile); adjacent tiles overlap by ov = k - 1 pixels which are
    summed.  With ov <= tile (checked in ``make_geometry``) the canvas block
    (i, j) of size tile x tile receives exactly four contributions:

      block(i,j)[:, :]        += tile(i,   j  )[:tile, :tile]   (body)
      block(i,j)[:, :ov]      += tile(i,   j-1)[:tile, tile:]   (left nbr)
      block(i,j)[:ov, :]      += tile(i-1, j  )[tile:, :tile]   (upper nbr)
      block(i,j)[:ov, :ov]    += tile(i-1, j-1)[tile:, tile:]   (diag nbr)

    The bottom/right canvas spill (rows/cols >= h_pad) is only dropped
    because ``make_geometry`` padded the canvas past every row the 'same'
    crop can read.
    """
    b, n, t, kk, _ = y_tiles.shape
    assert t == geo.n_tiles and kk == geo.fft_size
    ov = geo.ksize - 1
    tl = geo.tile
    th, tw = geo.n_tiles_h, geo.n_tiles_w
    yt = y_tiles.reshape(b, n, th, tw, kk, kk)

    def shift(a: Array, axis: int) -> Array:
        """a'[..., i, ...] = a[..., i-1, ...] with a'[..., 0, ...] = 0."""
        pad = [(0, 0)] * a.ndim
        pad[axis] = (1, 0)
        sl = [slice(None)] * a.ndim
        sl[axis] = slice(0, a.shape[axis])
        return jnp.pad(a, pad)[tuple(sl)]

    blk = yt[..., :tl, :tl]
    blk = blk.at[..., :, :ov].add(shift(yt[..., :tl, tl:], 3))
    blk = blk.at[..., :ov, :].add(shift(yt[..., tl:, :tl], 2))
    blk = blk.at[..., :ov, :ov].add(shift(shift(yt[..., tl:, tl:], 2), 3))

    out = blk.transpose(0, 1, 2, 4, 3, 5).reshape(b, n, geo.h_pad, geo.w_pad)

    # 'same' crop: same-output row i' reads full-conv row i' + (k-1-pad).
    start = geo.ksize - 1 - geo.pad
    h_out = geo.h_in + 2 * geo.pad - geo.ksize + 1
    w_out = geo.w_in + 2 * geo.pad - geo.ksize + 1
    return out[:, :, start:start + h_out, start:start + w_out]


# ---------------------------------------------------------------------------
# Hadamard stage (Eq 3) — reference path
# ---------------------------------------------------------------------------

def hadamard_accumulate(x_f: Array, w_f: Array) -> Array:
    """Eq 3:  Y~[b,n,t,u,v] = sum_m X~[b,m,t,u,v] * W~[n,m,u,v].

    Per frequency bin (u, v) this is a complex GEMM contracting input
    channels m — the formulation the TPU kernel exploits (MXU batched over
    frequency bins).  Here: plain einsum oracle.
    """
    return jnp.einsum("bmtuv,nmuv->bntuv", x_f, w_f)


# ---------------------------------------------------------------------------
# End-to-end spectral convolution
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("fft_size", "pad"))
def spectral_conv2d(x: Array, w: Array, *, fft_size: int = 8,
                    pad: int | None = None) -> Array:
    """Spectral convolution of NCHW ``x`` with spatial kernel ``w``.

    Equivalent (up to fp error) to 'same' cross-correlation, computed via
    FFT tiling + Hadamard + IFFT + OaA.
    """
    geo = make_geometry(x.shape[2], x.shape[3], w.shape[-1], fft_size, pad)
    w_f = spectral_kernel(w, fft_size)
    return spectral_conv2d_pretransformed(x, w_f, geo)


def spectral_conv2d_pretransformed(x: Array, w_f,
                                   geo: SpectralGeometry) -> Array:
    """Spectral conv with an already-transformed (possibly pruned) kernel.

    ``w_f`` is either a dense complex [N, M, K, K] array or a
    ``repro.core.sparse.SparseSpectralKernels`` (duck-typed on
    ``.values``/``.mask`` to avoid an import cycle).  For pruned kernels
    the Hadamard einsum is restricted to the frequency bins that are
    non-zero in *some* kernel — the whole-bin zero work (which the
    magnitude patterns of high-alpha layers concentrate at high
    frequencies) is skipped, so oracle benchmarks reflect sparsity.

    Uses overlap-save tiling (``extract_tiles_overlapping``), the
    repo-wide formulation since the fused-epilogue refactor: every output
    tile is complete after the IFFT, so a bias/ReLU epilogue can follow
    immediately.  For un-pruned kernels this equals the paper's OaA
    formulation (and ``spatial_conv2d``) exactly; for pruned kernels the
    two differ in where the circular wraparound of the full-support
    spectral kernel lands (DESIGN.md adaptation note 5) — this oracle
    defines the repo's pruned-conv semantics and the Pallas backends
    match it bit-for-bit in structure.
    """
    windows = extract_tiles_overlapping(x, geo)      # [B,M,T,K,K]
    x_f = jnp.fft.fft2(windows.astype(jnp.float32))  # [B,M,T,K,K]
    y_f = _hadamard_maybe_sparse(x_f, w_f, geo)      # [B,N,T,K,K]
    y_sp = jnp.fft.ifft2(y_f).real
    ov = geo.ksize - 1
    y_valid = y_sp[..., ov:, ov:]                    # [B,N,T,h',h']
    return assemble_valid_tiles(y_valid.astype(x.dtype), geo)


def _hadamard_maybe_sparse(x_f: Array, w_f, geo: SpectralGeometry) -> Array:
    if not hasattr(w_f, "values"):                   # dense kernel
        return hadamard_accumulate(x_f, w_f)
    values = w_f.values
    kk = geo.fft_size
    f = kk * kk
    # precomputed at prune time; deriving it here would pull the mask
    # back from device on every forward call
    active = getattr(w_f, "active_bins", None)
    if active is None:
        mask = w_f.mask
        if isinstance(mask, jax.core.Tracer):        # traced: stay dense
            return hadamard_accumulate(x_f, values)
        active = np.flatnonzero(np.asarray(mask).any(axis=(0, 1))
                                .reshape(f))
    if len(active) >= f:                             # nothing prunable
        return hadamard_accumulate(x_f, values)
    b, m, t = x_f.shape[:3]
    n = values.shape[0]
    xa = x_f.reshape(b, m, t, f)[..., active]
    wa = values.reshape(n, m, f)[..., active]
    ya = jnp.einsum("bmtf,nmf->bntf", xa, wa)
    y = jnp.zeros((b, n, t, f), ya.dtype)
    return y.at[..., active].set(ya).reshape(b, n, t, kk, kk)


@functools.partial(jax.jit, static_argnames=("pad", "stride"))
def spatial_conv2d(x: Array, w: Array, *, pad: int | None = None,
                   stride: int = 1) -> Array:
    """Spatial-domain oracle: 'same' cross-correlation.

    ``stride > 1`` is numerically identical to computing the stride-1
    'same' output and subsampling ``[..., ::stride, ::stride]`` — the
    exact contract of the spectral path's stride handling (the
    overlap-save kernel always produces the stride-1 output; see
    ``dataflow.ConvLayer.stride``).
    """
    k = w.shape[-1]
    if pad is None:
        pad = (k - 1) // 2
    return jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32),
        window_strides=(stride, stride),
        padding=[(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW")).astype(x.dtype)
