"""Plain reference of a spectral CNN configuration, in jax.numpy float32.

The mathematics of the configuration files that name ``spectral_cnn``,
written out without kernels, tables, plans, blocks or batching tricks,
and without importing the program under test:

- each 3x3 kernel is flipped, zero-padded to K x K and 2-D FFT'd; of each
  (c_out, c_in) kernel the K^2/alpha bins of largest magnitude are kept
  (the first in row-major order among equal magnitudes);
- a conv is overlap-save: K x K input windows at stride t = K - k + 1,
  starting k - 1 pixels above and left of the image; FFT; per bin the
  product with the kept kernel values, summed over input channels;
  inverse FFT; the real part's last t x t pixels of each window are the
  full-conv canvas, which is cropped to the 'same' output;
- then bias, stride subsampling, the shortcut add, ReLU; 2x2 max or
  average pools drop odd edges; the FC head is three dense layers with
  ReLU between them.

Matrix products run at ``precision="highest"`` (float32), or at
``"high"``: three bfloat16 passes (hi*hi + hi*lo + lo*hi), the control
that one step less precision must fail.

``network_work`` counts the work of the same mathematics from the shapes
alone (``bench/work.py`` reads it): overlap-save tiles of t = K - k + 1
output pixels, one K x K FFT per input channel and tile, a Hadamard
product over the K^2/alpha kept bins of each (c_out, c_in) kernel, one
inverse FFT per output channel and tile, and the three-layer FC head.
No block size, padding, table or layout of the program enters, so a PR
that changes the kernel leaves these numbers as they are.  Bytes are f32
(4 per real value, 8 per complex kernel value): each conv reads its input
activation once, its shortcut once where it has one, its pruned kernel
values once, and writes its output once; each FC layer reads its weights
once.  Every conv node runs as the fused spectral conv: its ``kind`` is
``"spectral"``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import graph

F32 = jnp.float32
PRECISIONS = ("highest", "high")
F32_BYTES = 4
C64_BYTES = 8


def make_params(cfg: dict, seed: int) -> dict:
    """Random weights in the layout the program takes, made on the device
    in one jitted call: conv weights N(0, 2/fan_in) [c_out, c_in, k, k],
    biases N(0, 0.01^2), FC weights N(0, 1/fan_in) [in, out]."""
    layers = cfg["layers"]
    fc = [i for i, _ in fc_dims(cfg)] + [cfg["n_classes"]]

    def init(key):
        ks = jax.random.split(key, 2 * len(layers) + 3)
        convs = []
        for i, l in enumerate(layers):
            shape = (l["c_out"], l["c_in"], l["ksize"], l["ksize"])
            fan_in = l["c_in"] * l["ksize"] ** 2
            convs.append({
                "w": jax.random.normal(ks[2 * i], shape, F32)
                * (2.0 / fan_in) ** 0.5,
                "b": jax.random.normal(ks[2 * i + 1], (l["c_out"],), F32)
                * 0.01})
        out = {"convs": convs}
        for j in range(3):
            out[f"fc{j + 1}"] = (jax.random.normal(
                ks[-3 + j], (fc[j], fc[j + 1]), F32) * fc[j] ** -0.5)
        return out

    return jax.jit(init)(jax.random.PRNGKey(seed))


def pruned_kernels(w: jax.Array, fft_size: int, alpha: float):
    """Kept spectral values of one layer as (re, im) [K*K, c_in, c_out]."""
    n, m, k, _ = w.shape
    kk = fft_size * fft_size
    nnz = kept_bins(fft_size, alpha)
    wf = jnp.fft.fft2(jnp.pad(w[..., ::-1, ::-1],
                              ((0, 0), (0, 0), (0, fft_size - k),
                               (0, fft_size - k))).astype(F32))
    mag = np.abs(np.asarray(wf)).reshape(n, m, kk)
    keep = np.argsort(-mag, axis=-1, kind="stable")[..., :nnz]
    mask = np.zeros((n, m, kk), bool)
    np.put_along_axis(mask, keep, True, axis=-1)
    vals = jnp.transpose(wf.reshape(n, m, kk) * jnp.asarray(mask), (2, 1, 0))
    return jnp.real(vals), jnp.imag(vals)


def _mm(a, b, precision: str):
    if precision == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)

    def split(x):
        # rounded by reduce_precision, which XLA keeps; a round trip
        # through astype(bfloat16) may be elided as excess precision
        bf16 = functools.partial(jax.lax.reduce_precision, exponent_bits=8,
                                 mantissa_bits=7)
        hi = bf16(x)
        return hi.astype(jnp.bfloat16), bf16(x - hi).astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)
    d = functools.partial(jnp.matmul, preferred_element_type=F32)
    return d(ah, bh) + d(ah, bl) + d(al, bh)


def _conv(x, wr, wi, layer: dict, fft_size: int, precision: str):
    """Overlap-save spectral conv, 'same' output at stride 1 (no bias)."""
    b, m, h, w = x.shape
    k, pad = layer["ksize"], layer["pad"]
    t, ov = fft_size - k + 1, k - 1
    nth, ntw = -(-(h + pad) // t), -(-(w + pad) // t)
    xp = jnp.pad(x, ((0, 0), (0, 0), (ov, nth * t - h), (ov, ntw * t - w)))
    ih = (np.arange(nth)[:, None] * t + np.arange(fft_size)).reshape(-1)
    iw = (np.arange(ntw)[:, None] * t + np.arange(fft_size)).reshape(-1)
    win = xp[:, :, ih][:, :, :, iw].reshape(b, m, nth, fft_size, ntw,
                                            fft_size)
    xf = jnp.fft.fft2(win.transpose(0, 1, 2, 4, 3, 5))   # [b,m,nth,ntw,K,K]
    kk = fft_size * fft_size
    xf = xf.reshape(b, m, nth * ntw, kk).transpose(3, 0, 2, 1)
    xf = xf.reshape(kk, b * nth * ntw, m)                # [K*K, b*T, m]
    xr, xi = jnp.real(xf), jnp.imag(xf)
    yr = _mm(xr, wr, precision) - _mm(xi, wi, precision)
    yi = _mm(xr, wi, precision) + _mm(xi, wr, precision)
    n = wr.shape[2]
    yf = (yr + 1j * yi).reshape(fft_size, fft_size, b, nth, ntw, n)
    y = jnp.fft.ifft2(yf.transpose(2, 5, 3, 4, 0, 1)).real[..., ov:, ov:]
    canvas = y.transpose(0, 1, 2, 4, 3, 5).reshape(b, n, nth * t, ntw * t)
    s = k - 1 - pad
    return canvas[:, :, s:s + h + 2 * pad - k + 1, s:s + w + 2 * pad - k + 1]


def _pool(x, kind: str):
    b, c, h, w = x.shape
    x = x[:, :, :h // 2 * 2, :w // 2 * 2].reshape(b, c, h // 2, 2, w // 2, 2)
    return x.max(axis=(3, 5)) if kind == "max" else x.mean(axis=(3, 5))


def forward(cfg: dict, kernels, params: dict, x, precision: str):
    layers = {l["name"]: (i, l) for i, l in enumerate(cfg["layers"])}
    acts = {"input": x}
    for node in graph.nodes(cfg):
        src = acts[node["inputs"][0]]
        if node["kind"] == "pool":
            y = _pool(src, node["pool"])
        else:
            i, layer = layers[node["id"]]
            y = _conv(src, *kernels[i], layer, cfg["fft_size"], precision)
            y = y + params["convs"][i]["b"][None, :, None, None]
            s = layer.get("stride", 1)
            y = y[:, :, ::s, ::s]
            if node["residual_from"]:
                y = y + acts[node["residual_from"]]
            if node["relu"]:
                y = jax.nn.relu(y)
        acts[node["id"]] = y
    y = acts[graph.nodes(cfg)[-1]["id"]].reshape(x.shape[0], -1)
    y = jax.nn.relu(_mm(y, params["fc1"], precision))
    y = jax.nn.relu(_mm(y, params["fc2"], precision))
    return _mm(y, params["fc3"], precision)


class Reference:
    """The configuration's forward pass over given weights."""

    def __init__(self, cfg: dict, params: dict):
        self.cfg, self.params = cfg, params
        self.kernels = [pruned_kernels(c["w"], cfg["fft_size"], cfg["alpha"])
                        for c in params["convs"]]
        self._fwd = {p: jax.jit(functools.partial(forward, cfg,
                                                  precision=p))
                     for p in PRECISIONS}

    def logits(self, images: np.ndarray, precision: str = "highest",
               block: int = 8) -> np.ndarray:
        """[n, classes] logits of ``images`` [n, C, H, W], ``block`` images
        to a call (the last block padded with zeros)."""
        out = []
        for i in range(0, len(images), block):
            x = np.zeros((block, *images.shape[1:]), np.float32)
            part = images[i:i + block]
            x[:len(part)] = part
            y = self._fwd[precision](self.kernels, self.params, x)
            out.append(np.asarray(y)[:len(part)])
        return np.concatenate(out)


# ---------------------------------------------------------------------------
# The work of one forward pass
# ---------------------------------------------------------------------------

def fft_flops(k: int) -> float:
    """Real flops of one K x K complex FFT at the radix-2 count, 5 N log2 N
    with N = K^2 points."""
    n = k * k
    return 5.0 * n * math.log2(n)


def kept_bins(fft_size: int, alpha: float) -> int:
    """Non-zeros kept per K x K spectral kernel."""
    return max(1, round(fft_size * fft_size / alpha))


def _residual_nodes(cfg: dict) -> set[str]:
    return {n["id"] for n in cfg.get("graph") or ()
            if n.get("residual_from")}


def conv_work(layer: dict, fft_size: int, alpha: float, *, batch: int = 1,
              residual: bool = False) -> dict:
    """Flops and HBM bytes of one conv node over ``batch`` images."""
    k, t = fft_size, fft_size - layer["ksize"] + 1
    h, w, stride = layer["h_in"], layer["w_in"], layer.get("stride", 1)
    tiles = math.ceil(h / t) * math.ceil(w / t)
    c_in, c_out = layer["c_in"], layer["c_out"]
    nnz = kept_bins(k, alpha)
    flops = batch * tiles * ((c_in + c_out) * fft_flops(k)
                             + 8.0 * nnz * c_in * c_out)
    out_px = math.ceil(h / stride) * math.ceil(w / stride)
    act = batch * (c_in * h * w + c_out * out_px * (2 if residual else 1))
    return {"name": layer["name"], "kind": "spectral", "flops": flops,
            "bytes": act * F32_BYTES + c_in * c_out * nnz * C64_BYTES,
            "pair_tiles": c_in * c_out * tiles}


def fc_dims(cfg: dict) -> list[tuple[int, int]]:
    """(in, out) of the three FC layers; the first takes the flattened
    output of the graph's last node."""
    return [(graph.feature_dim(cfg), cfg["fc_dim"]),
            (cfg["fc_dim"], cfg["fc_dim"]), (cfg["fc_dim"], cfg["n_classes"])]


def network_work(cfg: dict, *, batch: int = 1) -> dict:
    """Per-node and total work of one forward pass over ``batch`` images,
    in the form ``bench/work.py`` documents."""
    res = _residual_nodes(cfg)
    convs = [conv_work(l, cfg["fft_size"], cfg["alpha"], batch=batch,
                       residual=l["name"] in res) for l in cfg["layers"]]
    fc_flops = sum(2.0 * batch * i * o for i, o in fc_dims(cfg))
    fc_bytes = sum(i * o * F32_BYTES for i, o in fc_dims(cfg))
    conv_flops = sum(c["flops"] for c in convs)
    return {"convs": convs, "conv_flops": conv_flops,
            "conv_bytes": sum(c["bytes"] for c in convs),
            "fc_flops": fc_flops, "fc_bytes": fc_bytes,
            "flops": conv_flops + fc_flops}
