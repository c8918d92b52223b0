"""Device milliseconds of the fused conv executables (``_fused_conv``,
one per conv node) per image answered in the traced window."""

KERNEL = "_fused_conv"


def read(ctx):
    tr = ctx["trace"]
    s = sum(v for k, v in (tr or {}).get("module_s", {}).items()
            if KERNEL in k)
    if not s or not ctx["images"]:
        return None
    return 1e3 * s / ctx["images"]
