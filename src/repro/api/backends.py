"""The four built-in execution backends behind the `Executor` protocol.

  jax-dense    XLA matmul decode (raw params or dense CompressedFC leaves)
  pallas       compressed decode: int8 / codebook4 / acsr / aida leaves run
               through the Pallas LUT / ACSR-SpMV kernels (via dispatch)
  ap-emulator  bit-level CAM emulator of the paper's Fig. 3 algorithm
               (exact outputs AND exact cycle counts)
  cycle-sim    closed-form analytical cost models (aida_sim + eie_sim)

`ap-emulator` and `cycle-sim` agree on FC cycle counts by construction:
`cycle-sim` with the EMULATOR microcode reproduces the emulator's counter
exactly (the invariant tests/test_aida_fc.py asserts at module level, and
tests/test_api.py asserts through the facade).
"""
from __future__ import annotations

import numpy as np

from repro.api.registry import (Capabilities, CapabilityError, Executor,
                                register_backend)
from repro.api.spec import MODES, FCProblem, WORKLOADS


# --------------------------------------------------------------- decoders
class JaxDenseBackend(Executor):
    """Baseline XLA decode; FC layers as plain (bf16) matmuls."""
    name = "jax-dense"
    caps = Capabilities(batched_decode=True, modes=("dense",))

    def make_decode_step(self, cfg, unroll: bool = False, plan=None):
        from repro.models import model as M

        def serve_decode_step(params, state, tokens):
            return M.decode_step(cfg, params, state, tokens, unroll=unroll,
                                 plan=plan)
        return serve_decode_step

    def run_fc(self, layer, x):
        import jax.numpy as jnp
        if type(layer).__name__ == "CompressedFC":
            if layer.mode not in self.caps.modes:
                raise CapabilityError(
                    f"{self.name!r} only runs modes {self.caps.modes}; "
                    f"got {layer.mode!r} (use 'pallas')")
            w = layer.dense
        else:
            w = layer
        return jnp.matmul(x, w.T, preferred_element_type=jnp.float32)


class PallasBackend(JaxDenseBackend):
    """Compressed decode: CompressedFC leaves dispatch to the Pallas
    codebook-LUT / ACSR-SpMV kernels inside the same jitted step."""
    name = "pallas"
    caps = Capabilities(batched_decode=True, per_layer_override=True,
                        modes=MODES)

    def run_fc(self, layer, x):
        from repro.core.sparse_fc import apply_fc
        if type(layer).__name__ != "CompressedFC":
            raise CapabilityError(
                f"{self.name!r}.run_fc expects a CompressedFC layer")
        return apply_fc(layer, x)


# --------------------------------------------------------------- emulator
class APEmulatorBackend(Executor):
    """Bit-level associative-processor emulator (core.aida_fc): runs the
    paper's Fig. 3 FC algorithm op-by-op and measures exact cycles."""
    name = "ap-emulator"
    caps = Capabilities(cycle_accounting=True, modes=("aida",))

    def estimate(self, workload: FCProblem, **kw) -> dict:
        from repro.core import aida_fc
        if not isinstance(workload, FCProblem):
            raise CapabilityError(
                f"{self.name!r} estimates concrete FCProblem workloads; "
                f"use 'cycle-sim' for named workloads {WORKLOADS}")
        p = workload
        if p.coded:
            res = aida_fc.aida_fc_layer_coded(
                p.w, p.b, p.cents_w, p.cents_a, activation=p.activation)
            ref = aida_fc.fc_reference_coded(p.w, p.b, p.cents_w, p.cents_a,
                                             activation=p.activation)
        else:
            res = aida_fc.aida_fc_layer(p.w, p.b, m=p.m, n=p.n,
                                        activation=p.activation)
            ref = aida_fc.fc_reference(p.w, p.b, activation=p.activation)
        return {"backend": self.name, "cycles": res.cycles,
                "out": res.out, "reference": ref,
                "exact": bool(np.array_equal(res.out, ref)),
                "rounds": res.rounds, "nnz_b": res.nnz_b,
                "max_row_nnz": res.max_row_nnz,
                "counters": dict(res.counters)}


# -------------------------------------------------------------- cost model
class CycleSimBackend(Executor):
    """Closed-form analytical simulators: AIDA (aida_sim) and the EIE
    baseline (eie_sim).  Workloads: an FCProblem (per-layer cycle count,
    EMULATOR microcode by default — bit-exact vs 'ap-emulator'), a named
    network ('alexnet-fc' / 'ctc-lstm' / 'table1'), or a list of
    FCLayerSpec (PAPER microcode by default)."""
    name = "cycle-sim"
    caps = Capabilities(cycle_accounting=True, modes=("aida",))

    @staticmethod
    def _microcode(mc):
        from repro.core import aida_sim as S
        if mc is None or mc == "paper":
            return S.PAPER
        if mc == "emulator":
            return S.EMULATOR
        return mc  # a Microcode instance

    def estimate(self, workload, simulator: str = "aida",
                 microcode=None, **kw) -> dict:
        from repro.core import aida_sim as S
        from repro.core import eie_sim as E
        if isinstance(workload, FCProblem):
            if simulator != "aida":
                raise CapabilityError(
                    f"simulator {simulator!r} cannot price a bit-level "
                    "FCProblem; the EIE model takes FCLayerSpec networks")
            p = workload
            mc = self._microcode(microcode or "emulator")
            ph = S.cycles_fc(p.w.shape[1], p.nnz_b, p.max_row_nnz, mc,
                             mode="coded" if p.coded else "bitserial",
                             m=p.m, n=p.n, prod_bits=p.prod_bits)
            return {"backend": self.name, "simulator": simulator,
                    "cycles": ph.total(mc),
                    "phases": {"broadcast": ph.broadcast,
                               "multiply": ph.multiply,
                               "reduce": ph.reduce, "act": ph.act},
                    "nnz_b": p.nnz_b, "max_row_nnz": p.max_row_nnz}
        mc = self._microcode(microcode)
        if workload == "table1":
            return {"backend": self.name,
                    "aida": S.aida_table1(mc), "eie": E.eie_table1()}
        if isinstance(workload, str):
            if workload not in ("alexnet-fc", "ctc-lstm"):
                raise CapabilityError(
                    f"unknown workload {workload!r}; named workloads: "
                    f"{WORKLOADS}")
            layers = (S.alexnet_fc() if workload == "alexnet-fc"
                      else S.ctc_lstm())
            name = workload
        else:
            layers, name = list(workload), "custom"
        if simulator == "aida":
            rep = S.evaluate_network(name, layers, mc, **kw)
        elif simulator == "eie":
            rep = E.evaluate_network(name, layers, **kw)
        else:
            raise CapabilityError(
                f"unknown simulator {simulator!r}; choose 'aida' or 'eie'")
        return {"backend": self.name, "simulator": simulator,
                "report": rep,
                "cycles": rep.cycles_total,
                "inf_per_s": rep.inf_per_s}


register_backend(JaxDenseBackend())
register_backend(PallasBackend())
register_backend(APEmulatorBackend())
register_backend(CycleSimBackend())
