"""Sharded lower+compile inside pytest (8 host devices, subprocess).

The full 512-device matrix runs via ``repro.launch.dryrun``; this test
proves the same machinery (planner -> specs -> jit -> lower -> compile ->
HLO analysis) end to end on a small mesh so CI catches regressions
without the big compile bill.  XLA device count must be set before jax
initializes, hence the subprocess.
"""

import pathlib
import subprocess
import sys
import textwrap

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.distributed import ctx, planner, sharding
    from repro.launch import steps
    from repro.roofline import hlo_parse
    from repro.roofline.analysis import cost_analysis_dict

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    msd = {"data": 2, "model": 4}
    cfg = configs.get_smoke_config("qwen3-8b").replace(remat=True)
    shape = configs.ShapeConfig("t", seq_len=32, global_batch=4,
                                kind="train")
    plan = sharding.ShardingPlan(batch_axes=("data",))
    with mesh, ctx.use(ctx.ShardCtx(("data",))):
        fn, args = steps.cell_lowerable(cfg, shape, mesh, plan)
        compiled = fn.lower(*args).compile()
    cost = cost_analysis_dict(compiled)
    assert cost["flops"] > 0
    a = hlo_parse.parse(compiled.as_text(), 8)
    assert a.dot_flops > cost["flops"], (a.dot_flops, cost["flops"])
    assert a.collectives.wire_bytes_per_chip > 0
    # decode path too
    dshape = configs.ShapeConfig("d", seq_len=64, global_batch=2,
                                 kind="decode")
    with mesh:
        fn, args = steps.cell_lowerable(cfg, dshape, mesh, plan)
        compiled = fn.lower(*args).compile()
    assert cost_analysis_dict(compiled)["flops"] > 0
    print("LOWERING_OK")
""")


def test_sharded_lowering_8_devices():
    r = subprocess.run([sys.executable, "-c", SCRIPT],
                       env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "LOWERING_OK" in r.stdout
