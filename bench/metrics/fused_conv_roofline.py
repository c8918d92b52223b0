"""Percent of the fused conv's roofline: the least time the work of the
nodes that run as the fused spectral conv could take on this chip
(``bench/work.py``: per ``"spectral"`` node the larger of flops over peak
flop/s and bytes over peak HBM bandwidth), for every batch the window
ran, over the device time of the ``_fused_conv`` executables.  The peak
is the bf16 one; the program computes in f32."""

from bench import work

KERNEL = "_fused_conv"


def read(ctx):
    tr = ctx["trace"]
    s = sum(v for k, v in (tr or {}).get("module_s", {}).items()
            if KERNEL in k)
    if not s:
        return None
    least = sum(n * work.conv_least_time_s(ctx["cfg"], ctx["peaks"],
                                           batch=b)[0]
                for b, n in ctx["batches"].items())
    return 100.0 * least / s
