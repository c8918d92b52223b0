"""Bandwidth / on-chip-storage complexity models (paper §4, Eqs 6-13).

The paper analyses three pure dataflows for a sparse spectral conv layer —

  Flow #1  reuse kernels + partial sums, STREAM INPUT TILES
           (inputs re-loaded N/N' (pure) or N/Ns (flexible) times),
  Flow #2  reuse input tiles + partial sums, STREAM KERNELS
           (kernels re-loaded T/P' (pure) or T/Ps (flexible) times),
  Flow #3  reuse inputs + kernels, STREAM PARTIAL SUMS
           (psums written+read 2*M/M' times),

then interpolates between #1/#2 with the *streaming parameters* Ns (#kernels
resident before flushing input tiles) and Ps (#input tiles resident before
flushing kernels) — Eqs 12-13 — searched by Alg 1 (``repro.core.optimizer``).

Faithfulness notes
------------------
* Eqs 12/13 are implemented exactly as printed.  The pure-flow BRAM
  expressions (Eqs 6-8) are printed with garbled bank/depth placement in the
  source text; we implement the self-consistent reconstruction documented on
  each function (bank count x depth-overflow multiplier), which reproduces
  the paper's qualitative Fig 2: Flow #1 needs enormous BRAM counts on
  early (large-image) layers, Flow #2 few BRAMs but high traffic, Flow #3
  is never competitive.
* Data transfers are counted in 16-bit words as the paper does: spatial
  activations are real (1 word/value); spectral kernels and spectral psums
  are complex (2 words/value) — controlled by ``complex_words``.
* BRAM model: 36 Kb block = 1024 entries (paper's "memory depth 1024").

The same module also hosts the TPU re-cost of the flows used by the Pallas
kernel + mesh planner (HBM traffic / VMEM residency instead of DDR / BRAM):
see ``tpu_flow_cost``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Iterable

from repro.core.spectral import (halo_block_geometry, halo_block_starts,
                                 make_geometry, shard_band_rows)

BRAM_DEPTH = 1024
WORD_BYTES = 2  # 16-bit fixed point


@dataclasses.dataclass(frozen=True)
class ConvLayer:
    """Static description of one spectral conv layer.

    ``stride`` semantics (ISSUE 10): the spectral path always computes
    the stride-1 'same' output — overlap-save tiling has no native
    stride — and the executor subsamples ``y[..., ::stride, ::stride]``
    afterwards.  All tile/traffic/FLOP models therefore price the
    stride-1 problem, which is the work the kernel actually performs;
    only ``out_hw`` (and the DAG shape walker built on it) sees the
    stride.
    """

    name: str
    c_in: int       # M
    c_out: int      # N
    h_in: int
    w_in: int
    ksize: int = 3
    pad: int = 1
    stride: int = 1

    @property
    def out_hw(self) -> tuple[int, int]:
        """Post-stride output extent (the stride-1 'same' output is
        subsampled ``[::stride]`` -> ceil(h1/stride) rows survive)."""
        h1 = self.h_in + 2 * self.pad - self.ksize + 1
        w1 = self.w_in + 2 * self.pad - self.ksize + 1
        return (-(-h1 // self.stride), -(-w1 // self.stride))

    def tiles(self, fft_size: int) -> int:
        """T: number of input tiles per image (padded canvas)."""
        geo = make_geometry(self.h_in, self.w_in, self.ksize, fft_size,
                            self.pad)
        return geo.n_tiles

    def tile_size(self, fft_size: int) -> int:
        return fft_size - self.ksize + 1

    def spectral_macs(self, fft_size: int, alpha: float = 1.0) -> int:
        """Complex MACs of the (sparse) Hadamard stage (used to apportion
        the latency budget, Table 2 footnote)."""
        nnz = int(round(fft_size * fft_size / alpha))
        return self.tiles(fft_size) * nnz * self.c_in * self.c_out

    def spatial_macs(self) -> int:
        return (self.c_in * self.c_out * self.h_in * self.w_in
                * self.ksize * self.ksize)


# VGG16 conv stack (stride-1, pad-1, 3x3).  conv1_1 is omitted from dataflow
# optimization exactly as in the paper ("negligible computations").
VGG16_LAYERS: tuple[ConvLayer, ...] = (
    ConvLayer("conv1_1", 3, 64, 224, 224),
    ConvLayer("conv1_2", 64, 64, 224, 224),
    ConvLayer("conv2_1", 64, 128, 112, 112),
    ConvLayer("conv2_2", 128, 128, 112, 112),
    ConvLayer("conv3_1", 128, 256, 56, 56),
    ConvLayer("conv3_2", 256, 256, 56, 56),
    ConvLayer("conv3_3", 256, 256, 56, 56),
    ConvLayer("conv4_1", 256, 512, 28, 28),
    ConvLayer("conv4_2", 512, 512, 28, 28),
    ConvLayer("conv4_3", 512, 512, 28, 28),
    ConvLayer("conv5_1", 512, 512, 14, 14),
    ConvLayer("conv5_2", 512, 512, 14, 14),
    ConvLayer("conv5_3", 512, 512, 14, 14),
)

VGG16_OPT_LAYERS = VGG16_LAYERS[1:]


# ---------------------------------------------------------------------------
# DAG plan IR node description (ISSUE 10)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class NodeSpec:
    """Config-level description of one node of a network DAG
    (``models.cnn.SpectralCNNConfig.graph``).

    The linear VGG16 stack is the degenerate case: a chain of 'conv'
    nodes with 'pool' nodes interleaved.  ResNet-class graphs add
    residual edges: a conv node with ``residual_from`` set adds that
    node's activation into its own output BEFORE the ReLU — fused into
    the kernel's bias+ReLU flush when the plan can (see
    ``plan.EpilogueSpec.residual``), an unfused XLA add otherwise.

    Fields:
      id:       stable node id.  For 'conv' nodes this IS the name of
                the ``ConvLayer`` in ``cfg.layers`` the node executes
                (each conv layer appears in exactly one node).
      kind:     'conv' | 'pool'.
      inputs:   ids of the main-input producer(s); always length 1
                (the DAG is a chain plus shortcut edges).  The network
                input is the reserved id 'input'.
      pool:     pooling kind for 'pool' nodes, 'max' | 'avg' (2x2,
                stride 2 — the only pooling the spatial stage does).
      residual_from: for 'conv' nodes, the id of the node whose output
                is the shortcut operand (or 'input'); None = no
                shortcut.  Shapes must match the conv's POST-stride
                output.
      relu:     apply ReLU after this conv node (default).  False for
                linear nodes such as ResNet projection shortcuts.
    """

    id: str
    kind: str = "conv"
    inputs: tuple[str, ...] = ("input",)
    pool: str = "max"
    residual_from: str | None = None
    relu: bool = True

    def __post_init__(self):
        if self.kind not in ("conv", "pool"):
            raise ValueError(f"node {self.id!r}: kind must be 'conv' or "
                             f"'pool', got {self.kind!r}")
        if self.kind == "pool" and self.pool not in ("max", "avg"):
            raise ValueError(f"node {self.id!r}: pool must be 'max' or "
                             f"'avg', got {self.pool!r}")
        if len(self.inputs) != 1:
            raise ValueError(f"node {self.id!r}: exactly one main input "
                             f"required, got {self.inputs!r}")


def _ceil(a: float, b: float) -> int:
    return int(math.ceil(a / b))


# ---------------------------------------------------------------------------
# On-chip storage (Eqs 6-8, 12) — #BRAMs
# ---------------------------------------------------------------------------

def bram_flow1(layer: ConvLayer, fft_size: int, alpha: float,
               p_par: int, n_par: int, r: int, m_par: int = 1) -> int:
    """Flow #1 (Eq 6): kernels + psums resident, input tiles stream.

    banks x depth-multiplier reconstruction:
      inputs : r*M'*P'  streaming double-buffers (1 tile deep)
      kernels: M'*N' banks, all N kernels resident
               -> depth multiplier ceil(N * K^2/alpha / (N' * 1024))
      psums  : N'*P' banks, psums of every tile of the image resident
               -> depth multiplier ceil(T * K^2 / (P' * 1024))
    """
    k2 = fft_size * fft_size
    t = layer.tiles(fft_size)
    inp = r * m_par * p_par
    ker = m_par * n_par * max(1, _ceil(layer.c_out * k2 / alpha,
                                       n_par * BRAM_DEPTH))
    psum = n_par * p_par * max(1, _ceil(t * k2, p_par * BRAM_DEPTH))
    return inp + ker + psum


def bram_flow2(layer: ConvLayer, fft_size: int, alpha: float,
               p_par: int, n_par: int, r: int, m_par: int = 1) -> int:
    """Flow #2 (Eq 7): input tiles + psums resident, kernels stream.

      inputs : r*M'*P' banks, all T tiles resident
               -> depth multiplier ceil(T * K^2 / (P' * 1024))
      kernels: M'*N' streaming double-buffers
      psums  : N'*P' banks, N outputs for the P'-tile group resident
               -> depth multiplier ceil(N * K^2 / (N' * 1024))
    """
    k2 = fft_size * fft_size
    t = layer.tiles(fft_size)
    inp = r * m_par * p_par * max(1, _ceil(t * k2, p_par * BRAM_DEPTH))
    ker = m_par * n_par
    psum = n_par * p_par * max(1, _ceil(layer.c_out * k2, n_par * BRAM_DEPTH))
    return inp + ker + psum


def bram_flow3(layer: ConvLayer, fft_size: int, alpha: float,
               p_par: int, n_par: int, r: int, m_par: int = 1) -> int:
    """Flow #3 (Eq 8): inputs + kernels resident, psums stream.

    Eq 8 is a min over which of (inputs, kernels) is held whole:
      (a) all T input tiles resident + kernel double-buffer
      (b) input double-buffer + all N kernels resident
    with a psum streaming buffer of N'*P' banks either way.
    """
    k2 = fft_size * fft_size
    t = layer.tiles(fft_size)
    psum = n_par * p_par
    var_a = (r * m_par * p_par * max(1, _ceil(t * k2, p_par * BRAM_DEPTH))
             + m_par * n_par + psum)
    var_b = (r * m_par * p_par
             + m_par * n_par * max(1, _ceil(layer.c_out * k2 / alpha,
                                            n_par * BRAM_DEPTH))
             + psum)
    return min(var_a, var_b)


def bram_flexible(layer: ConvLayer, fft_size: int, alpha: float,
                  p_par: int, n_par: int, r: int,
                  ns: int, ps: int) -> int:
    """Eq 12: flexible flow with streaming parameters (Ns, Ps).

    As printed, plus the input-tile depth multiplier (Ps tiles resident
    across r replicas / P' parallel banks) which the printed equation
    folds into the bank count.
    """
    k2 = fft_size * fft_size
    inp = r * p_par * max(1, _ceil(ps * k2, p_par * BRAM_DEPTH))
    ker = n_par * max(1, _ceil(ns * k2 / alpha, n_par * BRAM_DEPTH))
    psum = n_par * p_par * max(1, _ceil(ns * ps * k2,
                                        n_par * p_par * BRAM_DEPTH))
    return inp + ker + psum


# ---------------------------------------------------------------------------
# Data transfers (Eqs 9-11, 13) — 16-bit words moved across DDR
# ---------------------------------------------------------------------------

def transfers_flow1(layer: ConvLayer, fft_size: int, alpha: float,
                    n_par: int, m_par: int = 1,
                    complex_words: int = 2) -> int:
    """Eq 9 numerator: inputs re-loaded once per N'-kernel group."""
    k2 = fft_size * fft_size
    reload_in = layer.c_out / n_par
    inp = layer.c_in * layer.h_in * layer.w_in * reload_in
    ker = layer.c_out * layer.c_in * k2 / alpha * complex_words
    out = layer.c_out * layer.h_in * layer.w_in
    return int(round(inp + ker + out))


def transfers_flow2(layer: ConvLayer, fft_size: int, alpha: float,
                    p_par: int, m_par: int = 1,
                    complex_words: int = 2) -> int:
    """Eq 10 numerator: kernels re-loaded once per P'-tile group."""
    k2 = fft_size * fft_size
    tile = layer.tile_size(fft_size)
    reload_k = (layer.h_in * layer.w_in) / (p_par * tile * tile)
    inp = layer.c_in * layer.h_in * layer.w_in
    ker = layer.c_out * layer.c_in * k2 / alpha * complex_words * reload_k
    out = layer.c_out * layer.h_in * layer.w_in
    return int(round(inp + ker + out))


def transfers_flow3(layer: ConvLayer, fft_size: int, alpha: float,
                    m_par: int = 1, complex_words: int = 2) -> int:
    """Eq 11 numerator: psums written + re-read once per input channel."""
    k2 = fft_size * fft_size
    inp = layer.c_in * layer.h_in * layer.w_in
    ker = layer.c_out * layer.c_in * k2 / alpha * complex_words
    out = (layer.c_out * layer.h_in * layer.w_in
           * 2 * (layer.c_in / m_par))
    return int(round(inp + ker + out))


def transfers_flexible(layer: ConvLayer, fft_size: int, alpha: float,
                       ns: int, ps: int, complex_words: int = 2) -> int:
    """Eq 13 numerator."""
    k2 = fft_size * fft_size
    tile = layer.tile_size(fft_size)
    inp = layer.c_in * layer.h_in * layer.w_in * (layer.c_out / ns)
    ker = (layer.c_out * layer.c_in * k2 / alpha * complex_words
           * (layer.h_in * layer.w_in) / (ps * tile * tile))
    out = layer.c_out * layer.h_in * layer.w_in
    return int(round(inp + ker + out))


def bandwidth_gbps(transfers_words: int, tau_s: float) -> float:
    """bw = #transfers / tau  (Eq at §4.2), in GB/s."""
    return transfers_words * WORD_BYTES / tau_s / 1e9


def layer_latency_budget(layers: Iterable[ConvLayer], fft_size: int,
                         alpha: float, total_tau_s: float) -> dict[str, float]:
    """tau_i = tau * CMP_i / CMP_total  (Table 2 footnote)."""
    layers = list(layers)
    cmps = {l.name: l.spectral_macs(fft_size, alpha) for l in layers}
    total = sum(cmps.values())
    return {n: total_tau_s * c / total for n, c in cmps.items()}


# ---------------------------------------------------------------------------
# TPU re-cost of the same three reuse choices (hardware adaptation)
# ---------------------------------------------------------------------------

# TPU v5e-class constants (also used by repro.roofline).
TPU_HBM_GBPS = 819e9
TPU_PEAK_FLOPS = 197e12
# Seconds per element of the 'scheduled' datapath's in-kernel table
# expansion (``fused_spectral_conv._TableBlock.read``).  Every grid step
# rebuilds its [bm, Fa, N'] kernel planes once per schedule cycle, with
# N' on the lanes padded to whole lane tiles: t_cyc * bm * Fa *
# lane_round(N') elements per step.  Per element, the one-hot
# [Fa, r] @ [r, N'] dot fills r of the MXU's 128 rows of depth and runs
# 6 bf16 passes at Precision.HIGHEST, 2 * 128 * 6 / TPU_PEAK_FLOPS =
# 7.8 ps; the VPU compares, the two weight FMAs and the VMEM round trip
# of the [bm, Fa, N'] carry make up the rest.  Fitted on a TPU v5e to
# the kernel time per node of spectral VGG16 and ResNet-18 at batch 1,
# all 33 conv nodes on tables (least squares of time = rate * elements
# + 11.1 ns per (n, m, p-block) kernel visit, t_cyc the model's own
# estimate): 9.8 ps.  So a 512->512 node spends 3.1 of its 6.0 ms
# expanding at N' = 128, and an input-stationary N' = 32 node pays for
# 128 lanes: 6.2 of 7.6 ms.  Streaming the same planes from HBM costs
# 512 B per kernel, ~0.6 ns at TPU_HBM_GBPS.
TABLE_EXPAND_S = 9.8e-12
# Alg 1's VMEM budget: the working set ``tpu_fused_flow_cost`` prices.
TPU_VMEM_BYTES = 48 * 2 ** 20
TPU_ICI_GBPS = 50e9
# Scoped-VMEM limit handed to Mosaic for every fused kernel.  A v5e
# TensorCore has 128 MiB of VMEM; the compiler's own temporaries (dot
# operands, Karatsuba sums) come on top of the working set Alg 1 prices
# against TPU_VMEM_BYTES, so the limit leaves them 3x room.  (Mosaic's
# default scoped limit, 16 MiB, is below the smallest legal working set
# of a 64-channel dense layer.)
TPU_VMEM_LIMIT_BYTES = 96 * 2 ** 20

# TPU vreg tiling: a block's minor (lane) dim is a multiple of LANES or
# the array's full extent, its second-minor (sublane) dim a multiple of
# SUBLANES or the full extent.  The fused kernel pads its tile (lane)
# axis to whole lane tiles; ``tiling_violations`` checks the rest.
LANES = 128
SUBLANES = 8


def lane_round(n: int) -> int:
    """``n`` rounded up to whole lane tiles."""
    return -(-n // LANES) * LANES


def kernel_lanes(layer: "ConvLayer", fft_size: int, block_p: int,
                 input_mode: str | None, batch: int = 1
                 ) -> tuple[int, int]:
    """(bp, gp): the fused kernel's lanes per grid step and p-grid size.

    'windowed' streams P = T*batch tiles in ``lane_round(min(block_p,
    P))``-lane blocks.  'halo' splits ``block_p`` into a bth x btw tile
    block (``halo_block_geometry``) whose tile columns are padded to a
    lane tile, so a step holds bth * lane_round(btw) lanes and the grid
    has one step per (image, block-row, block-col)."""
    if input_mode == "halo":
        geo = make_geometry(layer.h_in, layer.w_in, layer.ksize, fft_size,
                            layer.pad)
        hg = halo_block_geometry(geo, block_p)
        return hg.bth * lane_round(hg.btw), batch * hg.n_blocks
    t = layer.tiles(fft_size) * batch
    bp = lane_round(min(block_p, t))
    return bp, _ceil(t, bp)


def tiling_violations(layer: "ConvLayer", fft_size: int, block_n: int,
                      block_m: int, block_p: int, *, hadamard: str | None,
                      input_mode: str | None, geo=None) -> list[str]:
    """Blocks of the fused kernel that Mosaic would refuse, as messages
    (empty = legal).  Blocks are clamped to the layer like the kernel
    clamps them; a block equal to the clamped dim is the full extent
    (a full-extent M is padded to a sublane tile by the kernel).

      bias [N, 1] / planes [Fa, N, M] / shortcut [S2, N, P]: block_n on
        a sublane axis;
      planes: block_m on the lane axis; windows [S, M, P]: block_m on a
        sublane axis (scheduled tables hold M on a major axis);
      halo raw block [B, M, H, W]: its rows (sublanes) and columns
        (lanes), and the offsets it is read from — ``halo_block_geometry``
        / ``halo_block_starts`` of ``geo`` (default: the layer's own).
    The tile (lane) axis of every other block is padded by the kernel.
    """
    out = []
    bn, bm = min(block_n, layer.c_out), min(block_m, layer.c_in)
    if bn % SUBLANES and bn != layer.c_out:
        out.append(f"block_n={bn} on a sublane axis is neither a multiple "
                   f"of {SUBLANES} nor c_out={layer.c_out}")
    if hadamard != "scheduled" and bm % LANES and bm != layer.c_in:
        out.append(f"block_m={bm} on the planes' lane axis is neither a "
                   f"multiple of {LANES} nor c_in={layer.c_in}")
    if bm % SUBLANES and bm != layer.c_in:
        out.append(f"block_m={bm} on the windows' sublane axis is "
                   f"neither a multiple of {SUBLANES} nor "
                   f"c_in={layer.c_in}")
    if input_mode == "halo":
        if geo is None:
            geo = make_geometry(layer.h_in, layer.w_in, layer.ksize,
                                fft_size, layer.pad)
        hg = halo_block_geometry(geo, block_p)
        sh, sw = halo_block_starts(geo, hg)
        for axis, size, starts, full, tile in (
                ("rows", hg.rh, sh, geo.h_in, SUBLANES),
                ("cols", hg.rw, sw, geo.w_in, LANES)):
            if (size % tile and size != full) or (starts % tile).any():
                out.append(
                    f"halo block {axis}: {size} read from offsets "
                    f"{sorted({int(s) for s in starts})} are not aligned "
                    f"to {tile} (extent {full}, block_p={block_p})")
    return out

# Double-buffered VMEM accumulator slots the fused kernel's manual-DMA
# output path cycles through (PR 8): slot = linearized_grid_step %
# DMA_SLOTS, so an inbound psum prefetch for one step never collides
# with the previous step's write-back semaphore.  Shared by
# kernels.fused_spectral_conv (scratch allocation) and
# core.resilience.validate_plan (slot-budget invariant); lives here
# because both may not import each other.
DMA_SLOTS = 2

# Per-grid-step overhead priced into INTERPRET-mode plans, passed as
# ``tpu_fused_flow_cost(step_overhead_s=...)`` by the serving stack and
# the benchmarks.  Calibrated to ZERO: measured bucket sweeps
# (benchmarks/e2e_latency.py batch sweep on SMOKE) show interpret wall
# clock tracks the byte model's ranking — the serial windowed relayout
# dominates long before step count does — and because the predicted
# roofline times are microsecond-scale, ANY materially nonzero per-step
# price overturns byte preferences toward fewer-step windowed configs
# that are 2-3x slower on the wall clock.  Exact byte ties still
# resolve toward fewer dispatches structurally: autotune sorts on
# (predicted_s, grid_steps, hbm_bytes).  The step axis stays available
# through ``step_overhead_s`` for calibration on real hardware.
INTERPRET_STEP_S = 0.0

# The paper's three reuse choices as Pallas grid iteration orders —
# canonical name list shared by the kernels, the cost models below and
# the autotuner (core.autotune).
FLOWS = ("output_stationary", "weight_stationary", "input_stationary")

# Input-side modes of the fused kernel (kernels.fused_spectral_conv):
#   'windowed'  host materializes the [B, M, T, K, K] overlap-save
#               window tensor in HBM (one relayout pass + ~(K/t)^2
#               duplicated halo bytes), kernel streams windows;
#   'halo'      kernel reads the RAW NCHW activation via overlapping
#               (element-offset) input blocks sized bth*t + (K-t) per
#               spatial axis and gathers the windows in VMEM — no
#               windowed intermediate ever exists in HBM.
INPUT_MODES = ("windowed", "halo")

# Per-layer execution backends, in degradation-ladder order (see
# core.resilience.DEMOTION_LADDER): the fused single-pallas_call kernel,
# the 3-launch staged pipeline, and the pure-jnp einsum oracle — the
# terminal rung, which always executes.
EXEC_BACKENDS = ("fused", "staged", "einsum")


def tpu_flow_cost(layer: ConvLayer, fft_size: int, alpha: float,
                  block_n: int, block_p: int, block_m: int,
                  flow: str, batch: int = 1,
                  bytes_per_el: int = 4,
                  active_bins: int | None = None,
                  hadamard: str | None = None) -> dict[str, float]:
    """HBM traffic + VMEM residency of one spectral-Hadamard pallas_call.

    The Pallas kernel contracts input channels per frequency bin:
    ``Y[n,f,p] += W[n,m,f] X[m,f,p]`` with grid blocks (block_n x block_m x
    block_p).  The ``flow`` selects which operand stays resident across the
    grid's outermost iteration — the TPU translation of Flow #1/#2/#3:

      'weight_stationary' (Flow #1): W blocks stay in VMEM while all P
          blocks stream -> X re-read c_out/block_n times.
      'input_stationary'  (Flow #2): X blocks stay while kernel blocks
          stream -> W re-read T*batch/block_p times.
      'output_stationary' (Flow opt analogue): psums accumulate in VMEM
          across the m loop; X and W each read once per (n, p) block pair.

    Complex data: 2 real planes.  NOTE: the *staged* Pallas kernels
    stream and multiply DENSE spectral planes (pruned positions stored
    as zeros), so W traffic and FLOPs here are dense — ``alpha`` /
    ``active_bins`` are accepted for signature parity with
    ``tpu_fused_flow_cost`` (which IS sparsity-aware) and ignored.  The
    scheduled sparse kernel and the fused kernel's active-bin compaction
    are what turn compression into traffic/compute savings.
    ``hadamard`` is likewise accepted-and-ignored (the staged Hadamard
    has exactly one datapath).

    The tile axis P = T*batch is the planes' lane (minor) axis, so it is
    priced like the fused kernel's (``kernel_lanes``, 'windowed'): in
    ``lane_round(min(block_p, P))``-lane blocks, the last one padded.
    """
    del alpha, active_bins, hadamard  # dense-plane streaming only
    k2 = fft_size * fft_size
    bp, gp = kernel_lanes(layer, fft_size, block_p, "windowed", batch)
    t = bp * gp                       # tiles, padded to whole lane blocks
    cplx = 2
    x_bytes = layer.c_in * k2 * t * cplx * bytes_per_el
    w_bytes = layer.c_out * layer.c_in * k2 * cplx * bytes_per_el
    y_bytes = layer.c_out * k2 * t * cplx * bytes_per_el

    if flow == "weight_stationary":
        hbm = (x_bytes * math.ceil(layer.c_out / block_n)
               + w_bytes + y_bytes)
    elif flow == "input_stationary":
        hbm = (x_bytes + w_bytes * gp + y_bytes)
    elif flow == "output_stationary":
        hbm = (x_bytes * math.ceil(layer.c_out / block_n)
               + w_bytes * gp + y_bytes)
    else:
        raise ValueError(flow)

    # per-grid-step working set: ONE frequency bin's blocks (the Pallas
    # grid blocks F with size 1; see kernels/spectral_hadamard.py)
    vmem = (block_m * bp * cplx * bytes_per_el                  # X block
            + block_n * block_m * cplx * bytes_per_el           # W block
            + block_n * bp * cplx * 4)                          # f32 acc
    flops = 8 * t * k2 * layer.c_in * layer.c_out
    return {
        "hbm_bytes": float(hbm),
        "vmem_bytes": float(vmem),
        "flops": float(flops),
        "hbm_s": float(hbm) / TPU_HBM_GBPS,
        "compute_s": float(flops) / TPU_PEAK_FLOPS,
        "fits_vmem": vmem <= TPU_VMEM_BYTES,
    }


# Hadamard-stage modes of the fused kernel (kernels.fused_spectral_conv):
#   'dense'      full-K^2 kernel planes, Karatsuba GEMM;
#   'bin'        planes compacted to the Fa active bins, Karatsuba GEMM;
#   'scheduled'  Alg-2 INDEX/VALUE tables executed element-granularly.
HADAMARD_MODES = ("dense", "bin", "scheduled")

# Default Alg-2 knobs for analytic costing (paper S6.3: r = 10 replicas;
# mu ~= Eq-14 PE utilization, VGG16 measures ~0.85-0.9 — used to
# estimate schedule length T ~= nnz / mu before the schedule is built).
SCHEDULE_R = 10
SCHEDULE_MU = 0.85


def tpu_fused_flow_cost(layer: ConvLayer, fft_size: int, alpha: float,
                        block_n: int, block_p: int, block_m: int,
                        flow: str, batch: int = 1,
                        bytes_per_el: int = 4,
                        active_bins: int | None = None,
                        hadamard: str | None = None,
                        r: int = SCHEDULE_R,
                        mu: float = SCHEDULE_MU,
                        input_mode: str | None = None,
                        step_overhead_s: float = 0.0,
                        residual: str | None = None) -> dict[str, float]:
    """HBM traffic + VMEM working set of ONE fused pallas_call
    (``kernels.fused_spectral_conv``): FFT + Hadamard + IFFT (+ fused
    bias/ReLU epilogue) in a single kernel, so HBM only ever sees

      X  overlap-save windows [S, M, P]  real,  S = K^2, P = T * batch
      W  the Hadamard-stage kernel operand (planes or Alg-2 tables)
      Y  valid output tiles [S2, N, P]   real,  S2 = tile^2

    — the complex spectral intermediates X~/Y~ of the staged path
    (``tpu_flow_cost``'s x/y terms) never leave VMEM, and the post-conv
    elementwise epilogue adds no traffic at all.

    Args:
      layer, fft_size, alpha: the conv layer, tile size K and kernel
        compression ratio (nnz = K^2/alpha per kernel).
      block_n/block_p/block_m: VMEM block sizes (the paper's N'/P'/M');
        clamped to the layer dims.
      flow: grid iteration order, one of ``FLOWS``.
      batch: images per call (scales the tile count P).
      active_bins: Fa <= K^2, the compacted bin count realized by this
        layer's pruned kernels (``sparse.compacted_active_bins``); None
        means all K^2 bins.  Scales the spectral-transform dims
        (FFT/IFFT flops, spectral VMEM blocks, operator residency).
      hadamard: Hadamard-stage mode (``HADAMARD_MODES``), controlling
        the kernel-operand traffic and Hadamard MAC terms:
          None          legacy compressed-stream model: kernel bytes and
                        MACs ~ nnz (the paper's (value, index) stream),
                        kept for back-compat with pre-mode callers;
          'dense'       full K^2 planes — bytes and MACs ~ K^2;
          'bin'         compacted planes — bytes and MACs ~ Fa (what the
                        Karatsuba GEMM on active bins actually does);
          'scheduled'   Alg-2 tables — bytes ~ T*(r + 3N') words per
                        (group, channel) with T ~= nnz/mu cycles, i.e.
                        O(nnz); MACs are the plane GEMM on the Fa bins,
                        and the kernel first expands the tables into
                        those planes on every grid step: ``expand_s``,
                        t_cyc * bm * Fa * lane_round(N') elements per
                        step at ``TABLE_EXPAND_S`` each, added to
                        ``compute_s``.  The mode saves bytes and pays
                        in expansion time; Alg 1 sees both sides.
      r, mu: Alg-2 replica count and estimated Eq-14 utilization used
        to size the scheduled tables before the schedule exists.
      input_mode: input-side path (``INPUT_MODES``), controlling the
        X-operand traffic:
          None / 'windowed'  the host materializes the [B, M, T, K, K]
                       overlap-save window tensor: ONE relayout pass
                       (raw read + windowed write, counted once) plus
                       the kernel's window stream of T*K^2 words per
                       channel — ~(K/t)^2 more than the raw image —
                       re-read per the flow factor below;
          'halo'       the kernel reads the raw activation through
                       overlapping halo blocks (bth*t + k - 1 rows by
                       btw*t + k - 1 cols, ``halo_block_geometry``
                       split of block_p): raw-plus-halo words, re-read
                       per the same flow factor, plus the one-hot
                       gather selectors once; no materialization pass
                       exists at all.
      step_overhead_s: fixed cost per GRID STEP (dispatch + pipeline
        prologue + per-step DMA issue), added to the predicted latency
        as ``grid_steps * step_overhead_s`` (the ``step_s`` field).
        Default 0.0 keeps the pure byte/flop roofline; serving and the
        interpret-mode benchmarks pass ``INTERPRET_STEP_S`` (itself
        calibrated to zero — see its comment — but kept as the single
        knob for real-hardware calibration).  At larger batch the step
        count per image shrinks with bigger p blocks, which is exactly
        the kernel-amortization axis of the paper's reuse tradeoff.
      residual: shortcut-operand pricing for a residual-fused epilogue
        (ISSUE 10, the ShortcutFusion reuse question one operand over):
          None     no shortcut — the plain conv cost;
          'hbm'    the shortcut streams from HBM as one more kernel
                   operand in the OUTPUT layout: one Y-sized read per
                   output-block visit (once total under
                   output_stationary, once per m revisit under the RMW
                   flows, whose flush step re-sees each (n, p) block
                   gm times), plus its double-buffered VMEM block;
          'vmem'   the producer's activation is modeled as RETAINED
                   on-chip between the two kernels — zero extra HBM
                   traffic, but the full Y-sized shortcut is added to
                   the VMEM working set (the ShortcutFusion "hold the
                   shortcut" choice; it only wins while it fits).

    Batch amortization note: ``batch`` scales the tile count
    P = T * batch, so every per-whole-call byte term that does NOT
    scale with P — kernel planes / Alg-2 tables (ws streams them ONCE
    per call, i.e. once per batch, not once per image) and the one-off
    selector/materialization bytes — is amortized over the batch in the
    returned ``per_image_*`` fields.  That is SPEC2's kernel-reuse
    prediction: per-image fused cost is non-increasing in batch (along
    the doubling bucket chain; see ``tests/test_batch_amortized.py``).

    Returns a dict with ``hbm_bytes``, ``kernel_hbm_bytes`` (the
    W-operand share of hbm_bytes, re-read factors included),
    ``input_hbm_bytes`` (the X-operand share: stream * re-read factor
    + the one-off materialization / gather-selector bytes),
    ``had_flops`` (Hadamard stage only), ``flops``, ``vmem_bytes``,
    ``hbm_s``/``compute_s`` roofline times (``compute_s`` = flops at
    ``TPU_PEAK_FLOPS`` + ``expand_s``, the scheduled mode's table
    expansion; 0 in the other modes), ``serial_s``,
    ``fits_vmem``, plus (PR 8) ``batch``, ``grid_steps`` (= gn*gm*gp,
    the pallas grid size — the tuner's dispatch-overhead tie-break),
    ``step_s`` and the batch-normalized ``per_image_hbm_bytes`` /
    ``per_image_kernel_hbm_bytes`` / ``per_image_s``.  ``serial_s`` is the windowed path's materialization
    pass: an XLA relayout op that runs BEFORE the pallas_call and
    cannot overlap it, so its time adds to the roofline max instead of
    hiding under it (``serial_s + max(hbm_s, compute_s)`` is the
    honest per-layer latency; the halo path has serial_s = 0 — its
    gather selectors stream through the kernel's own pipeline).

    Re-read factors follow the grid iteration order of each flow:

      'output_stationary': psums in VMEM scratch; X re-read per n block,
          W re-read per p block, Y written exactly once.
      'weight_stationary' (Flow #1, reuse kernels): W read once; X
          re-read per n block; real psum tiles RMW'd once per m block
          (2*gm - 1 passes).
      'input_stationary'  (Flow #2, reuse activations): X read once; W
          re-read per p block; same psum RMW traffic.
    """
    if hadamard is not None and hadamard not in HADAMARD_MODES:
        raise ValueError(f"hadamard must be None or one of "
                         f"{HADAMARD_MODES}, got {hadamard!r}")
    if input_mode is not None and input_mode not in INPUT_MODES:
        raise ValueError(f"input_mode must be None or one of "
                         f"{INPUT_MODES}, got {input_mode!r}")
    if residual not in (None, "hbm", "vmem"):
        raise ValueError(f"residual must be None, 'hbm' or 'vmem', "
                         f"got {residual!r}")
    halo = input_mode == "halo"
    k2 = fft_size * fft_size
    tile = layer.tile_size(fft_size)
    t = layer.tiles(fft_size) * batch
    cplx = 2
    nnz = max(1, int(round(k2 / alpha)))
    fa = k2 if active_bins is None else max(1, min(int(active_bins), k2))
    gn = max(1, _ceil(layer.c_out, block_n))
    gm = max(1, _ceil(layer.c_in, block_m))
    bn = min(block_n, layer.c_out)
    bm = min(block_m, layer.c_in)
    if bm == layer.c_in:             # full-extent M: kernel pads to 8
        bm = -(-bm // SUBLANES) * SUBLANES
    # Lanes the kernel actually computes: the tile axis padded to whole
    # lane tiles (windowed) or each halo tile row padded to one
    # (``kernel_lanes``).  Every per-tile term below prices p_pad.
    bp, gp = kernel_lanes(layer, fft_size, block_p, input_mode, batch)
    p_pad = gp * bp
    m_pad = gm * bm
    s = k2                   # overlap-save: K x K input windows
    s2 = tile * tile         # only the valid rows are written back
    raw_words = layer.c_in * layer.h_in * layer.w_in * batch
    if halo:
        geo = make_geometry(layer.h_in, layer.w_in, layer.ksize,
                            fft_size, layer.pad)
        hg = halo_block_geometry(geo, block_p)
        lw = lane_round(hg.btw)
        # raw-plus-halo words: every block reads its bth*t+k-1 x
        # btw*t+k-1 clamped raw region; overlap between neighbours is
        # the k-1 halo only, and nothing is materialized first.
        x_stream = (m_pad * batch * hg.n_blocks * hg.rh * hg.rw
                    * bytes_per_el)
        # One-hot selector traffic is residency-aware: a selector block
        # is refetched only when its block index changes between
        # consecutive grid steps, so a single-block axis stays resident
        # for the whole kernel; otherwise it re-streams with the p
        # steps (upper bound: every p step, times the n revisits).
        sel_reread = {"output_stationary": gn * gp,
                      "weight_stationary": gn * gm * gp,
                      "input_stationary": gp}.get(flow, gp)
        gr_words = hg.bth * fft_size * hg.rh
        gc_words = hg.rw * fft_size * lw
        x_once = ((gr_words * (1 if hg.nbh == 1 else sel_reread))
                  + (gc_words * (1 if hg.nbw == 1 else sel_reread))
                  ) * bytes_per_el
    else:
        # windowed: the kernel streams the host-materialized, lane-
        # padded window tensor; the relayout pass that builds it (raw
        # read + windowed write) happens once, outside the kernel.
        x_stream = m_pad * s * p_pad * bytes_per_el
        x_once = (raw_words + layer.c_in * s * t) * bytes_per_el
    y_bytes = layer.c_out * s2 * p_pad * bytes_per_el

    t_cyc = max(nnz, _ceil(nnz, mu))     # schedule length estimate
    expand_s = 0.0
    if hadamard is None:                 # legacy compressed stream
        w_bytes = layer.c_out * layer.c_in * nnz * cplx * bytes_per_el
        had_flops = 8 * p_pad * nnz * layer.c_in * layer.c_out
    elif hadamard == "dense":         # planes padded to (gn*bn, m_pad)
        w_bytes = gn * bn * m_pad * k2 * cplx * bytes_per_el
        had_flops = 8 * p_pad * k2 * m_pad * layer.c_out
    elif hadamard == "bin":
        w_bytes = gn * bn * m_pad * fa * cplx * bytes_per_el
        had_flops = 8 * p_pad * fa * m_pad * layer.c_out
    else:                                # scheduled: Alg-2 tables
        w_bytes = gn * m_pad * t_cyc * (r + 3 * bn) * bytes_per_el
        # The kernel expands each table block into [Fa, bm, N'] planes
        # in VMEM on every grid step — per cycle one [Fa, r] @ [r, N']
        # one-hot matmul per channel plus two weight FMAs, N' padded to
        # whole lanes — then runs the plane GEMM on the Fa compacted
        # bins.  Every flow expands again on every p step.
        expand_s = (gn * gm * gp * t_cyc * bm * fa * lane_round(bn)
                    * TABLE_EXPAND_S)
        had_flops = 8 * p_pad * fa * m_pad * gn * bn

    if flow == "output_stationary":
        x_hbm = x_stream * gn + x_once
        hbm = x_hbm + w_bytes * gp + y_bytes
        w_hbm = w_bytes * gp
    elif flow == "weight_stationary":
        x_hbm = x_stream * gn + x_once
        hbm = x_hbm + w_bytes + y_bytes * (2 * gm - 1)
        w_hbm = w_bytes
    elif flow == "input_stationary":
        x_hbm = x_stream + x_once
        hbm = x_hbm + w_bytes * gp + y_bytes * (2 * gm - 1)
        w_hbm = w_bytes * gp
    else:
        raise ValueError(flow)

    # Shortcut operand of a residual-fused epilogue: Y-layout blocks,
    # consumed at the flush step of each output block.  Under the RMW
    # flows the flush dimension is innermost, so the block is refetched
    # on every m revisit; output_stationary sees each (n, p) exactly
    # once.  'vmem' instead retains the producer's full activation
    # on-chip (zero HBM, Y-sized VMEM residency).
    sc_hbm = 0.0
    sc_vmem = 0.0
    if residual == "hbm":
        sc_reread = 1 if flow == "output_stationary" else gm
        sc_hbm = float(y_bytes * sc_reread)
        sc_vmem = float(2 * s2 * bn * bp * bytes_per_el)
        x_hbm += sc_hbm
        hbm += sc_hbm
    elif residual == "vmem":
        sc_vmem = float(y_bytes)

    # Streamed blocks are double-buffered by the Pallas pipeline (x2);
    # the DFT operators, the in-flight spectral values and the psum
    # scratch are single-copy VMEM residents.  Spectral dims are Fa.
    if hadamard == "scheduled":
        w_block = bm * t_cyc * (r + 3 * bn)       # table block
        flight = 3 * bm * fa * bn                 # expanded planes + hit
    else:
        w_block = cplx * fa * bn * bm             # W plane block
        flight = fa * bn * bm                     # Karatsuba wr + wi
    if halo:
        # raw halo block instead of a window block; one window row's
        # masked raw block [bm, rh, rw], one tile row's column gathers
        # [K, bm, K*lw], the double-buffered selector blocks and the
        # windows live in VMEM in flight.
        x_block = bm * hg.rh * hg.rw
        flight += (s * bm * bp + bm * hg.rh * hg.rw
                   + bm * fft_size * fft_size * lw
                   + 2 * (gr_words + gc_words))
    else:
        x_block = s * bm * bp
        flight += s * bm * bp
    scratch = {"output_stationary": cplx * fa * bn * bp,
               "input_stationary": cplx * fa * bm * bp}.get(flow, 0)
    vmem = (2 * (x_block + w_block)               # pipelined input blocks
            + DMA_SLOTS * s2 * bn * bp            # manual-DMA Y staging
            + scratch                             # psums / FFT-once X~
            + (cplx + 1) * fa * bm * bp           # X~ + Karatsuba sum
            + 3 * fa * bn * bp                    # Karatsuba products
            + s2 * bn * bp                        # IFFT'd block
            + flight
            + 2 * fa * s + 2 * s2 * fa            # DFT / IDFT operators
            ) * bytes_per_el + sc_vmem            # retained / staged shortcut

    refft = gn if flow != "input_stationary" else 1
    fft_flops = 2 * 2 * fa * s * m_pad * p_pad * refft
    if halo:
        # the in-kernel gather (row mask-and-sum per window row, column
        # one-hot matmul per window row), recomputed whenever the
        # block's FFT is
        gather_macs = gp * gm * (bm * hg.bth * fft_size * hg.rh * hg.rw
                                 + hg.bth * bm * fft_size * hg.rw
                                 * fft_size * lw)
        fft_flops += 2 * gather_macs * refft
    ifft_passes = 1 if flow == "output_stationary" else gm
    ifft_flops = 2 * 2 * s2 * fa * gn * bn * p_pad * ifft_passes
    flops = had_flops + fft_flops + ifft_flops
    serial = 0 if halo else x_once      # windowed relayout pass: serial
    grid_steps = gn * gm * gp
    step_s = float(grid_steps) * float(step_overhead_s)
    hbm_s = float(hbm - serial) / TPU_HBM_GBPS
    serial_s = float(serial) / TPU_HBM_GBPS
    compute_s = float(flops) / TPU_PEAK_FLOPS + expand_s
    total_s = serial_s + step_s + max(hbm_s, compute_s)
    return {
        "hbm_bytes": float(hbm),
        "kernel_hbm_bytes": float(w_hbm),
        "input_hbm_bytes": float(x_hbm),
        "input_mode": "halo" if halo else "windowed",
        "had_flops": float(had_flops),
        "vmem_bytes": float(vmem),
        "flops": float(flops),
        "hbm_s": hbm_s,
        "serial_s": serial_s,
        "compute_s": compute_s,
        "expand_s": expand_s,
        "fits_vmem": vmem <= TPU_VMEM_BYTES,
        # --- residual-shortcut pricing (ISSUE 10) ---------------------
        "residual": residual,
        "shortcut_hbm_bytes": sc_hbm,
        "shortcut_vmem_bytes": sc_vmem,
        # --- batch-as-an-Alg-1-axis fields (PR 8) ---------------------
        "batch": int(batch),
        "grid_steps": float(grid_steps),
        "step_s": step_s,
        "per_image_hbm_bytes": float(hbm) / batch,
        "per_image_kernel_hbm_bytes": float(w_hbm) / batch,
        "per_image_s": total_s / batch,
    }


# ---------------------------------------------------------------------------
# Two-level Alg-1: per-chip HBM + ICI bytes for a sharded layer (ISSUE 9)
# ---------------------------------------------------------------------------

# Per-layer partitioning strategies over a 1-D device mesh of D shards:
#   'replicate'  every chip runs the whole layer (terminal rung of the
#                sharded degradation ladder; also the only legal choice
#                when neither split is feasible) — no ICI traffic, no
#                per-chip savings;
#   'channel'    split the input channels M: shard d owns c_in/D
#                channels and the matching kernel slice, computes a
#                PARTIAL conv (epilogue deferred) and ring-all-reduces
#                the psum — the TPU translation of the paper's Flow-#3
#                psum streaming, with the stream crossing ICI instead
#                of DDR.  Feasible iff D divides c_in;
#   'spatial'    split the tile rows: shard d owns a band of
#                ceil(n_tiles_h/D) tile rows and receives the k-1 raw
#                halo rows of its top neighbour over ICI before the
#                conv (the PR-5 in-kernel halo gather's geometry, one
#                level up).  Feasible iff the tile grid has at least
#                one tile row per shard.
SHARD_STRATEGIES = ("replicate", "channel", "spatial")


def shard_local_layer(layer: ConvLayer, fft_size: int, n_shards: int,
                      strategy: str) -> "ConvLayer | None":
    """The shard-local sub-problem of ``layer`` as a ConvLayer, or None
    when ``strategy`` is infeasible at ``n_shards``.

    The returned layer is what ONE chip computes — feed it to
    ``tpu_fused_flow_cost`` for the per-chip level of the two-level
    model.  'channel' shrinks c_in; 'spatial' shrinks h_in to
    ``tr*t - pad`` (the unique height whose ``make_geometry`` tile grid
    is exactly the shard's tr = ceil(n_tiles_h/D) tile rows — the band's
    k-1 in-buffer halo rows are ICI-accounted, not HBM-re-modeled).
    """
    if strategy not in SHARD_STRATEGIES:
        raise ValueError(f"strategy must be one of {SHARD_STRATEGIES}, "
                         f"got {strategy!r}")
    if strategy == "replicate" or n_shards <= 1:
        return layer
    if strategy == "channel":
        if layer.c_in % n_shards:
            return None
        return dataclasses.replace(layer, c_in=layer.c_in // n_shards)
    geo = make_geometry(layer.h_in, layer.w_in, layer.ksize, fft_size,
                        layer.pad)
    if n_shards > geo.n_tiles_h:
        return None
    tr = shard_band_rows(geo, n_shards)
    return dataclasses.replace(layer, h_in=tr * geo.tile - layer.pad)


def shard_ici_bytes(layer: ConvLayer, n_shards: int, strategy: str,
                    batch: int = 1, bytes_per_el: int = 4,
                    residual: bool = False) -> float:
    """Modeled inter-chip bytes of one sharded layer forward.

      'replicate'  0 — nothing crosses ICI.
      'channel'    ring all-reduce of the [B, N, H_out, W_out] psum:
                   each chip sends (and receives) 2*(D-1)/D of the
                   output bytes (reduce-scatter + all-gather).
      'spatial'    each interior boundary moves exactly the k-1 raw
                   halo rows one hop down: (D-1) * (k-1) * W * M * B
                   words (outputs stay resident — bands concatenate
                   only at the consumer, which is itself band-sharded).

    ``residual`` (ISSUE 10): a residual add on a non-replicated layer
    moves the Y-sized shortcut into the shards' layout — one more
    (D-1)/D all-gather-shaped term on top of the strategy's own
    collective (replicate pays nothing: the shortcut is already whole
    on every chip).
    """
    if strategy == "replicate" or n_shards <= 1:
        return 0.0
    h_out = layer.h_in + 2 * layer.pad - layer.ksize + 1
    w_out = layer.w_in + 2 * layer.pad - layer.ksize + 1
    out_bytes = layer.c_out * h_out * w_out * batch * bytes_per_el
    sc = ((n_shards - 1) / n_shards * out_bytes) if residual else 0.0
    if strategy == "channel":
        return 2.0 * (n_shards - 1) / n_shards * out_bytes + sc
    if strategy == "spatial":
        return float((n_shards - 1) * (layer.ksize - 1) * layer.w_in
                     * layer.c_in * batch * bytes_per_el) + sc
    raise ValueError(f"strategy must be one of {SHARD_STRATEGIES}, "
                     f"got {strategy!r}")


def tpu_sharded_flow_cost(layer: ConvLayer, fft_size: int, alpha: float,
                          block_n: int, block_p: int, block_m: int,
                          flow: str, *, n_shards: int, strategy: str,
                          batch: int = 1, bytes_per_el: int = 4,
                          active_bins: int | None = None,
                          hadamard: str | None = None,
                          r: int = SCHEDULE_R, mu: float = SCHEDULE_MU,
                          input_mode: str | None = None,
                          step_overhead_s: float = 0.0,
                          residual: str | None = None
                          ) -> "dict[str, float] | None":
    """Two-level Alg-1 cost: ONE CHIP's ``tpu_fused_flow_cost`` of the
    shard-local sub-problem, plus the ICI collective priced at
    ``TPU_ICI_GBPS``.  Returns None when ``strategy`` is infeasible at
    ``n_shards`` (channel: D must divide c_in; spatial: at least one
    tile row per shard).

    Adds to the per-chip cost dict:
      'strategy' / 'n_shards'   the partitioning priced,
      'per_chip_hbm_bytes'      alias of the local 'hbm_bytes',
      'ici_bytes' / 'ici_s'     the collective's bytes and serialized
                                time (ICI does not overlap the fused
                                kernel today: channel's all-reduce
                                waits on the full psum, spatial's halo
                                exchange precedes the conv),
      'sharded_s'               the two-level objective
                                per-chip predicted + ici_s.
    """
    local = shard_local_layer(layer, fft_size, n_shards, strategy)
    if local is None:
        return None
    c = tpu_fused_flow_cost(local, fft_size, alpha, block_n, block_p,
                            block_m, flow, batch=batch,
                            bytes_per_el=bytes_per_el,
                            active_bins=active_bins, hadamard=hadamard,
                            r=r, mu=mu, input_mode=input_mode,
                            step_overhead_s=step_overhead_s,
                            residual=residual)
    ici = shard_ici_bytes(layer, n_shards, strategy, batch, bytes_per_el,
                          residual=residual is not None)
    chip_s = c["serial_s"] + c["step_s"] + max(c["hbm_s"], c["compute_s"])
    c.update({
        "strategy": strategy,
        "n_shards": int(n_shards),
        "per_chip_hbm_bytes": c["hbm_bytes"],
        "ici_bytes": float(ici),
        "ici_s": float(ici) / TPU_ICI_GBPS,
        "sharded_s": chip_s + float(ici) / TPU_ICI_GBPS,
    })
    return c
