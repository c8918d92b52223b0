"""Percent of the chip's peak flop/s that the whole forward pass's
counted flops (``bench/work.py``: every conv node, of any kind, and the
head, per image) reach at the traced window's image rate.  The peak is
the bf16 one; the program computes in f32."""

from bench import work


def read(ctx):
    if not ctx["images"] or ctx["window_s"] <= 0:
        return None
    flops = work.network_work(ctx["cfg"])["flops"] * ctx["images"]
    return 100.0 * flops / ctx["window_s"] / ctx["peaks"]["flops_per_s"]
