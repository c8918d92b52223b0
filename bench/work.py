"""The work a configuration's forward pass needs, from its shapes alone.

Counts follow the algorithm, not an implementation: overlap-save tiles
of t = K - k + 1 output pixels, one K x K FFT per input channel and tile,
a Hadamard product over the K^2/alpha kept bins of each (c_out, c_in)
kernel, one inverse FFT per output channel and tile, and a dense FC head.
No block size, padding, table or layout of the program enters, so a PR
that changes the kernel leaves these numbers as they are.

Bytes are f32 (4 per real value, 8 per complex kernel value): each conv
reads its input activation once, its shortcut once where it has one,
its pruned kernel values once, and writes its output once; each FC layer
reads its weights once.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

from bench import graph

F32 = 4
C64 = 8
PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


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
    return {"name": layer["name"], "flops": flops,
            "bytes": act * F32 + c_in * c_out * nnz * C64,
            "pair_tiles": c_in * c_out * tiles}


def fc_dims(cfg: dict) -> list[tuple[int, int]]:
    """(in, out) of the three FC layers; the first takes the flattened
    output of the graph's last node."""
    return [(graph.feature_dim(cfg), cfg["fc_dim"]),
            (cfg["fc_dim"], cfg["fc_dim"]), (cfg["fc_dim"], cfg["n_classes"])]


def network_work(cfg: dict, *, batch: int = 1) -> dict:
    """Per-node and total work of one forward pass over ``batch`` images."""
    res = _residual_nodes(cfg)
    convs = [conv_work(l, cfg["fft_size"], cfg["alpha"], batch=batch,
                       residual=l["name"] in res) for l in cfg["layers"]]
    fc_flops = sum(2.0 * batch * i * o for i, o in fc_dims(cfg))
    fc_bytes = sum(i * o * F32 for i, o in fc_dims(cfg))
    conv_flops = sum(c["flops"] for c in convs)
    return {"convs": convs, "conv_flops": conv_flops,
            "conv_bytes": sum(c["bytes"] for c in convs),
            "fc_flops": fc_flops, "fc_bytes": fc_bytes,
            "flops": conv_flops + fc_flops}


def peaks_for(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; an unknown
    kind is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"{PEAKS_FILE.name} (known: {sorted(table)})")
    return table[device_kind]


def least_time_s(flops: float, nbytes: float, peaks: dict
                 ) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    tf, tb = flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")


def conv_least_time_s(cfg: dict, peaks: dict, *, batch: int = 1
                      ) -> tuple[float, dict[str, int]]:
    """Sum over conv nodes of each node's least time, and how many nodes
    each bound sets."""
    total, bounds = 0.0, {"flops": 0, "bytes": 0}
    for c in network_work(cfg, batch=batch)["convs"]:
        s, bound = least_time_s(c["flops"], c["bytes"], peaks)
        total += s
        bounds[bound] += 1
    return total, bounds
