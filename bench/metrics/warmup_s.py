"""Host seconds of ``SpectralServer.warm_forward()`` and one request per
bucket through submit/tick: compile-cache loads and first calls."""


def read(ctx):
    return ctx["warmup_s"]
