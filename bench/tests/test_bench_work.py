"""Work counts and peaks of the benchmark (bench/work.py, bench/peaks.json,
and the spectral CNN reference's count in bench/configs/spectral_cnn.py)."""

import dataclasses
import json
from pathlib import Path

import pytest

from bench import work
from bench.configs import spectral_cnn

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def load(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_vgg16_counts_match_the_worked_figures():
    w = work.network_work(load("vgg16-spectral"))
    pair_tiles = sum(c["pair_tiles"] for c in w["convs"])
    assert pair_tiles == 54_909_696                  # ~55 M c_in*c_out*T
    hadamard = 8 * 16 * pair_tiles                   # 16 kept bins, 8 flops
    assert hadamard == pytest.approx(7.03e9, rel=1e-3)
    kernel_bytes = sum(l["c_in"] * l["c_out"] for l in
                       load("vgg16-spectral")["layers"]) * 16 * 8
    assert kernel_bytes == pytest.approx(209e6, rel=1e-2)
    assert w["conv_bytes"] - kernel_bytes == pytest.approx(90e6, rel=1e-2)
    assert w["fc_bytes"] == pytest.approx(494.5e6, rel=1e-3)
    # FFTs add (c_in + c_out) * T * 5 N log2 N on top of the Hadamard
    assert w["conv_flops"] > hadamard
    peaks = work.peaks_for("TPU v5 lite")
    least, bounds = work.conv_least_time_s(load("vgg16-spectral"), peaks)
    assert bounds == {"flops": 0, "bytes": 13}       # B=1: bytes-bound
    floor = least + w["fc_bytes"] / peaks["hbm_bytes_per_s"]
    assert floor == pytest.approx(0.97e-3, rel=0.02)  # ~1 ms per image


def test_resnet18_counts():
    cfg = load("resnet18-spectral")
    w = work.network_work(cfg)
    assert sum(c["pair_tiles"] for c in w["convs"]) == 38_247_168
    assert spectral_cnn.fc_dims(cfg) == [(25088, 512), (512, 512),
                                         (512, 1000)]
    # residual nodes also read their shortcut
    by = {c["name"]: c for c in w["convs"]}
    plain = spectral_cnn.conv_work(cfg["layers"][1], 8, 4.0)
    assert by["s1b1b"]["bytes"] - plain["bytes"] == 64 * 112 * 112 * 4


# the counts as they stood when bench/work.py itself counted every config
PINNED = {
    "vgg16-spectral": {
        "fc_dims": [(25088, 4096), (4096, 4096), (4096, 1000)],
        "pair_tiles": 54_909_696,
        1: ({"conv_flops": 8338180608.0, "conv_bytes": 299732992,
             "fc_flops": 247267328.0, "fc_bytes": 494534656,
             "flops": 8585447936.0},
            (0.0003659743492063492, {"flops": 0, "bytes": 13})),
        8: ({"conv_flops": 66705444864.0, "conv_bytes": 933355520,
             "fc_flops": 1978138624.0, "fc_bytes": 494534656,
             "flops": 68683583488.0},
            (0.0011396282295482296, {"flops": 0, "bytes": 13}))},
    "resnet18-spectral": {
        "fc_dims": [(25088, 512), (512, 512), (512, 1000)],
        "pair_tiles": 38_247_168,
        1: ({"conv_flops": 6045633024.0, "conv_bytes": 282390528,
             "fc_flops": 27238400.0, "fc_bytes": 54476800,
             "flops": 6072871424.0},
            (0.0003447991794871795, {"flops": 0, "bytes": 20})),
        8: ({"conv_flops": 48365064192.0, "conv_bytes": 857006080,
             "fc_flops": 217907200.0, "fc_bytes": 54476800,
             "flops": 48582971392.0},
            (0.0010464054700854702, {"flops": 0, "bytes": 20}))},
}


@pytest.mark.parametrize("name", list(PINNED))
def test_the_references_count_keeps_the_pinned_totals(name):
    cfg, pin = load(name), PINNED[name]
    peaks = work.peaks_for("TPU v5 lite")
    assert spectral_cnn.fc_dims(cfg) == pin["fc_dims"]
    for batch in (1, 8):
        w = work.network_work(cfg, batch=batch)
        assert w == spectral_cnn.network_work(cfg, batch=batch)
        assert {k: v for k, v in w.items() if k != "convs"} == pin[batch][0]
        assert sum(c["pair_tiles"] for c in w["convs"]) == pin["pair_tiles"]
        assert [c["name"] for c in w["convs"]] == [
            l["name"] for l in cfg["layers"]]
        assert {c["kind"] for c in w["convs"]} == {"spectral"}
        assert work.conv_least_time_s(cfg, peaks, batch=batch) == (
            pin[batch][1])


def test_batch_reads_kernels_once():
    cfg = load("vgg16-spectral")
    one = work.network_work(cfg, batch=1)
    eight = work.network_work(cfg, batch=8)
    assert eight["conv_flops"] == pytest.approx(8 * one["conv_flops"])
    assert eight["conv_bytes"] < 8 * one["conv_bytes"]


def _layers_of(plan):
    return [dataclasses.asdict(lp.layer) for lp in plan.layers]


def test_count_ignores_the_plans_blocks_and_hadamard_mode():
    """Two plans of one config that differ in Hadamard datapath and
    tuning describe the same shapes, so the same work."""
    import jax
    from repro.configs import vgg16_spectral
    from repro.core.plan import build_network_plan
    from repro.models import cnn
    pc = vgg16_spectral.SMOKE
    params = cnn.init(jax.random.PRNGKey(0), pc)
    a = build_network_plan(params, pc, hadamard="dense")
    b = build_network_plan(params, pc, hadamard="scheduled", blocks=(8,))
    assert ({lp.hadamard for lp in a.layers}
            != {lp.hadamard for lp in b.layers})
    assert [lp.tuning for lp in a.layers] != [lp.tuning for lp in b.layers]
    base = {"reference": "spectral_cnn",
            "image_size": pc.image_size, "fft_size": pc.fft_size,
            "alpha": pc.alpha, "n_classes": pc.n_classes,
            "fc_dim": pc.fc_dim, "pool_after": sorted(pc.pool_after)}
    wa = work.network_work({**base, "layers": _layers_of(a)})
    wb = work.network_work({**base, "layers": _layers_of(b)})
    assert wa == wb


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks_for("TPU v9 imaginary")
    p = work.peaks_for("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "source" in p


def test_least_time_names_its_bound():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time_s(1000.0, 10.0, peaks) == (10.0, "flops")
    assert work.least_time_s(10.0, 1000.0, peaks) == (100.0, "bytes")
