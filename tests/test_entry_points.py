"""Entry-point behaviour that needs no chip: where the persistent
compilation cache lives, and that ``chip_smoke.py`` refuses to run on
anything but a TPU.  Each case runs in a fresh interpreter (JAX reads
its cache settings once, at start-up)."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

_PROBE = """
import jax
from repro.launch.compile_cache import enable_compile_cache
first, second = enable_compile_cache(), enable_compile_cache()
print(first); print(second); print(jax.config.jax_compilation_cache_dir)
"""


def _run(args, *, cache_dir=None, cwd=ROOT):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    return subprocess.run([sys.executable] + args, env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=120)


def test_compile_cache_follows_the_environment(tmp_path):
    r = _run(["-c", _PROBE], cache_dir=tmp_path)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [str(tmp_path)] * 3


def test_compile_cache_defaults_to_the_checkout():
    r = _run(["-c", _PROBE])
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [str(ROOT / ".jax_cache")] * 3
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_chip_smoke_refuses_the_cpu():
    r = _run([str(ROOT / "chip_smoke.py")])
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_chip_smoke_needs_the_repository(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env_root = tmp_path / "chip_smoke.py"
    r = subprocess.run([sys.executable, str(env_root)], cwd=tmp_path,
                       env={"PATH": os.environ.get("PATH", ""),
                            "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert not [line for line in r.stdout.splitlines()
                if line.startswith("{") and json.loads(line).get("ok")]


def test_chip_smoke_four_chips_on_a_forced_mesh():
    """The four-chip phase end to end on four virtual CPU devices, at
    SMOKE size with the kernels interpreted: all three partitionings
    within the smoke's own tolerance of the one-chip forward."""
    script = ("import chip_smoke as cs\n"
              "from repro.configs import vgg16_spectral\n"
              "cs.four_chips(vgg16_spectral.SMOKE, interpret=True)\n")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    for name in ("spatial", "auto", "channel"):
        assert f"ok    {name}: sharded logits within" in r.stdout
