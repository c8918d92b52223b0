"""The work a configuration's forward pass needs, and the chip's peaks.

A configuration's work is counted by its own plain reference: the module
``bench/configs/<reference>.py`` that the file's ``reference`` names
provides ``network_work(cfg, *, batch)``, which counts from the shapes
alone, never from a block size, table or layout of the program.  It
returns

- ``convs``: one entry per conv node, ``{"name", "kind", "flops",
  "bytes", ...}`` over ``batch`` images.  ``kind`` is the node's
  algorithm: ``"spectral"`` for a node that runs as the fused spectral
  conv (``_fused_conv``), another name (such as ``"dense"``) for one that
  does not;
- ``conv_flops``, ``conv_bytes``: their sums;
- ``fc_flops``, ``fc_bytes``: the head's;
- ``flops``: the whole forward pass's.

The fused conv's roofline (``conv_least_time_s``) sums only the
``"spectral"`` nodes; the whole pass's share of the peak reads
``flops``.
"""

from __future__ import annotations

import importlib
import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
SPECTRAL = "spectral"


def network_work(cfg: dict, *, batch: int = 1) -> dict:
    """Per-node and total work of one forward pass over ``batch`` images,
    as the configuration's reference counts it."""
    ref = importlib.import_module(f"bench.configs.{cfg['reference']}")
    return ref.network_work(cfg, batch=batch)


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
    """Sum over the ``"spectral"`` conv nodes of each node's least time,
    and how many of them each bound sets."""
    total, bounds = 0.0, {"flops": 0, "bytes": 0}
    for c in network_work(cfg, batch=batch)["convs"]:
        if c["kind"] != SPECTRAL:
            continue
        s, bound = least_time_s(c["flops"], c["bytes"], peaks)
        total += s
        bounds[bound] += 1
    return total, bounds
