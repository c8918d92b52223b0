"""docs/DATAFLOW.md stays truthful: its per-flow byte formulas are
extracted from the page's ``doc-formulas`` fenced block, executed, and
compared against ``dataflow.tpu_fused_flow_cost`` over layers x flows x
Hadamard modes x input modes.  If the cost model changes without the
document, or vice versa, this fails.
"""

import re
from pathlib import Path

import pytest

from repro.core import dataflow as df
from repro.core import spectral as spec

DOC = Path(__file__).resolve().parent.parent / "docs" / "DATAFLOW.md"

_BLOCK = re.compile(r"```python doc-formulas\n(.*?)```", re.DOTALL)
_BLOCK_ICI = re.compile(r"```python doc-formulas-ici\n(.*?)```",
                        re.DOTALL)


def _doc_namespace() -> dict:
    m = _BLOCK.search(DOC.read_text())
    assert m, "docs/DATAFLOW.md lost its ```python doc-formulas block"
    ns: dict = {}
    exec(compile(m.group(1), str(DOC), "exec"), ns)  # noqa: S102
    for fn in ("input_bytes", "kernel_bytes", "output_bytes",
               "per_image_bytes", "expand_seconds", "step_seconds"):
        assert fn in ns, f"doc-formulas block lost {fn}()"
    return ns


def _doc_ici_namespace() -> dict:
    m = _BLOCK_ICI.search(DOC.read_text())
    assert m, "docs/DATAFLOW.md lost its ```python doc-formulas-ici " \
              "block (section 8)"
    ns: dict = {}
    exec(compile(m.group(1), str(DOC), "exec"), ns)  # noqa: S102
    for fn in ("ici_bytes", "ici_seconds", "sharded_seconds"):
        assert fn in ns, f"doc-formulas-ici block lost {fn}()"
    return ns


CASES = [(layer, flow, mode, imode, batch)
         for layer in (df.VGG16_LAYERS[1], df.VGG16_LAYERS[5],
                       df.VGG16_LAYERS[-1])
         for flow in df.FLOWS
         for mode in df.HADAMARD_MODES
         for imode in df.INPUT_MODES
         for batch in (1, 8)]


class TestDocFormulasMatchCode:
    ns = _doc_namespace()

    @pytest.mark.parametrize("layer,flow,mode,imode,batch", CASES,
                             ids=[f"{l.name}-{f}-{m}-{i}-b{b}"
                                  for l, f, m, i, b in CASES])
    def test_shares_and_total(self, layer, flow, mode, imode, batch):
        fft, alpha = 8, 4.0
        block_n, block_p, block_m = 64, 128, 64
        step_overhead_s = 1e-4
        c = df.tpu_fused_flow_cost(layer, fft, alpha, block_n, block_p,
                                   block_m, flow, batch=batch,
                                   hadamard=mode, input_mode=imode,
                                   step_overhead_s=step_overhead_s)
        geo = spec.make_geometry(layer.h_in, layer.w_in, layer.ksize,
                                 fft, layer.pad)
        hg = spec.halo_block_geometry(geo, block_p)
        T = geo.n_tiles * batch
        k2 = fft * fft
        nnz = max(1, round(k2 / alpha))
        bn = min(block_n, layer.c_out)
        bm = min(block_m, layer.c_in)
        if bm == layer.c_in:                   # full extent: 8 sublanes
            bm = -(-bm // df.SUBLANES) * df.SUBLANES
        gn = max(1, -(-layer.c_out // block_n))
        gm = max(1, -(-layer.c_in // block_m))
        # the actual p grid and its lanes (lane-padded tile axis)
        bp, gp = df.kernel_lanes(layer, fft, block_p, imode, batch)
        mp = gm * bm

        x = self.ns["input_bytes"](
            flow, layer.c_in, layer.h_in, layer.w_in, fft, T, batch,
            imode, hg.nbh, hg.nbw, hg.rh, hg.rw, hg.bth,
            df.lane_round(hg.btw), gn, gm, mp, gp * bp)
        w = self.ns["kernel_bytes"](
            flow, layer.c_out, layer.c_in, fft, k2, nnz,
            df.SCHEDULE_MU, df.SCHEDULE_R, bn, mp, gp, mode)
        y = self.ns["output_bytes"](flow, layer.c_out, geo.tile,
                                    gp * bp, gm)

        assert x == pytest.approx(c["input_hbm_bytes"]), "input share"
        assert w == pytest.approx(c["kernel_hbm_bytes"]), "kernel share"
        assert x + w + y == pytest.approx(c["hbm_bytes"]), "total"

        # batch amortization (S1b): per-image shares divide by B
        pt, pk = self.ns["per_image_bytes"](x + w + y, w, batch)
        assert pt == pytest.approx(c["per_image_hbm_bytes"]), "per-image"
        assert pk == pytest.approx(c["per_image_kernel_hbm_bytes"]), \
            "per-image kernel"

        # interpret-mode step pricing: step_s = gn*gm*gp * overhead
        assert self.ns["step_seconds"](
            gn, gm, gp, step_overhead_s) == pytest.approx(c["step_s"]), \
            "step_s"
        assert c["grid_steps"] == pytest.approx(gn * gm * gp), \
            "grid_steps"

        # the scheduled datapath's table expansion, inside compute_s
        assert self.ns["expand_seconds"](
            gn, gm, gp, nnz, df.SCHEDULE_MU, bm, k2, bn,
            mode) == pytest.approx(c["expand_s"]), "expand_s"
        assert self.ns["TABLE_EXPAND_S"] == df.TABLE_EXPAND_S

    def test_doc_is_linked(self):
        """README and ARCHITECTURE must point at the walkthrough."""
        root = DOC.parent.parent
        assert "docs/DATAFLOW.md" in (root / "README.md").read_text()
        assert "DATAFLOW.md" in (root / "docs" /
                                 "ARCHITECTURE.md").read_text()


ICI_CASES = [(layer, strategy, n_shards, batch)
             for layer in (df.VGG16_LAYERS[1], df.VGG16_LAYERS[5],
                           df.VGG16_LAYERS[-1])
             for strategy in df.SHARD_STRATEGIES
             for n_shards in (2, 4, 8)
             for batch in (1, 8)]


class TestDocIciFormulasMatchCode:
    """Section 8's two-level formulas (wire bytes per strategy, ICI
    serialization, the sharded objective) against the code."""

    ns = _doc_ici_namespace()

    @pytest.mark.parametrize("layer,strategy,n_shards,batch", ICI_CASES,
                             ids=[f"{l.name}-{s}-D{d}-b{b}"
                                  for l, s, d, b in ICI_CASES])
    def test_ici_and_objective(self, layer, strategy, n_shards, batch):
        fft, alpha = 8, 4.0
        doc_wire = self.ns["ici_bytes"](
            strategy, n_shards, layer.c_out, layer.c_in, layer.h_in,
            layer.w_in, layer.ksize, layer.pad, batch)
        assert doc_wire == pytest.approx(df.shard_ici_bytes(
            layer, n_shards, strategy, batch)), "wire bytes"
        assert self.ns["ICI_BYTES_PER_S"] == df.TPU_ICI_GBPS

        c = df.tpu_sharded_flow_cost(
            layer, fft, alpha, 64, 128, 64, "output_stationary",
            n_shards=n_shards, strategy=strategy, batch=batch,
            step_overhead_s=1e-4)
        if c is None:       # infeasible split: doc feasibility matches
            assert strategy != "replicate"
            if strategy == "channel":
                assert layer.c_in % n_shards != 0
            else:
                geo = spec.make_geometry(layer.h_in, layer.w_in,
                                         layer.ksize, fft, layer.pad)
                assert n_shards > geo.n_tiles_h
            return
        assert c["ici_bytes"] == pytest.approx(doc_wire)
        assert c["ici_s"] == pytest.approx(
            self.ns["ici_seconds"](doc_wire))
        assert c["sharded_s"] == pytest.approx(self.ns["sharded_seconds"](
            c["serial_s"], c["step_s"], c["hbm_s"], c["compute_s"],
            c["ici_s"])), "two-level objective"
