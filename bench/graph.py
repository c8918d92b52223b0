"""The node graph a configuration file describes, and its shapes.

A file either lists ``graph`` nodes (conv and pool nodes, with shortcut
edges), or only ``layers`` and ``pool_after``: a chain of the convs with a
2x2 max-pool after each layer named there.
"""

from __future__ import annotations


def nodes(cfg: dict) -> list[dict]:
    """Topologically ordered nodes: ``{"id", "kind", "inputs", "pool",
    "residual_from", "relu"}``; a conv node's id is its layer's name."""
    if cfg.get("graph"):
        return [{"pool": "max", "residual_from": None, "relu": True, **n}
                for n in cfg["graph"]]
    out, prev = [], "input"
    for layer in cfg["layers"]:
        out.append({"id": layer["name"], "kind": "conv", "inputs": [prev],
                    "pool": "max", "residual_from": None, "relu": True})
        prev = layer["name"]
        if prev in cfg.get("pool_after", ()):
            out.append({"id": f"{prev}:pool", "kind": "pool",
                        "inputs": [prev], "pool": "max",
                        "residual_from": None, "relu": True})
            prev = f"{prev}:pool"
    return out


def shapes(cfg: dict) -> dict[str, tuple[int, int, int]]:
    """(C, H, W) of every node's output, ``"input"`` included."""
    first = cfg["layers"][0]
    out = {"input": (first["c_in"], cfg["image_size"], cfg["image_size"])}
    layers = {l["name"]: l for l in cfg["layers"]}
    for n in nodes(cfg):
        c, h, w = out[n["inputs"][0]]
        if n["kind"] == "pool":
            out[n["id"]] = (c, h // 2, w // 2)
        else:
            s = layers[n["id"]].get("stride", 1)
            out[n["id"]] = (layers[n["id"]]["c_out"], -(-h // s), -(-w // s))
    return out


def feature_dim(cfg: dict) -> int:
    """Flattened size of the last node's output, which enters the FC head."""
    c, h, w = shapes(cfg)[nodes(cfg)[-1]["id"]]
    return c * h * w
