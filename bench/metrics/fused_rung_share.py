"""Percent of the images the server answered on the fused rung
(``served_by_rung``); a demotion to the staged or einsum rung shows here
first."""


def read(ctx):
    served = ctx["stats"]["served_by_rung"]
    total = sum(served.values())
    return 100.0 * served["fused"] / total if total else None
