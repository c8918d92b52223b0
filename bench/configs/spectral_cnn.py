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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import graph

F32 = jnp.float32
PRECISIONS = ("highest", "high")


def make_params(cfg: dict, seed: int) -> dict:
    """Random weights in the layout the program takes, made on the device
    in one jitted call: conv weights N(0, 2/fan_in) [c_out, c_in, k, k],
    biases N(0, 0.01^2), FC weights N(0, 1/fan_in) [in, out]."""
    layers = cfg["layers"]
    fc = [graph.feature_dim(cfg), cfg["fc_dim"], cfg["fc_dim"],
          cfg["n_classes"]]

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
    nnz = max(1, round(kk / alpha))
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
