"""MoE dispatch as the paper's SpMM — the AESPA technique inside an LM.

Shows the correspondence end-to-end (DESIGN.md §4):
1. run an olmoe-style MoE layer and capture its routing decisions;
2. expose the routing matrix as the paper's U_T C_E compressed tensor;
3. run the combine through the EIE-like SpMM Pallas kernel and verify it
   matches the MoE layer's own gather/scatter arithmetic;
4. ask the AESPA scheduler which dataflow class it would pick for the
   dispatch matmul given the routing sparsity.

    PYTHONPATH=src python examples/moe_hetero.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_reduced
from repro.core import dse
from repro.core.scheduler import schedule_single_kernel
from repro.core.workloads import Workload
from repro.kernels import ops
from repro.models import moe as M


def main() -> None:
    cfg = get_reduced("olmoe-1b-7b")
    p = M.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    out, (weights, idx) = M.moe_mlp(p, x, cfg, None)
    t = weights.shape[0]
    print(f"MoE: {cfg.n_experts} experts, top-{cfg.experts_per_token}, "
          f"{t} tokens routed")

    # The routing matrix IS a U_T C_E compressed tensor (density k/E).
    ell = M.routing_as_ell(weights, idx, cfg.n_experts)
    density = float(ell.density())
    print(f"routing matrix: {ell.shape}, density={density:.3f} "
          f"(= k/E = {cfg.experts_per_token / cfg.n_experts:.3f})")

    # Combine == EIE-like SpMM of R (sparse) with expert outputs (dense).
    summaries = jax.random.normal(jax.random.PRNGKey(2),
                                  (cfg.n_experts, cfg.d_model))
    via_spmm = ops.spmm_mirror(ell, summaries, bm=32, bn=64)
    dense_r = np.zeros(ell.shape, np.float32)
    for ti in range(t):
        for j in range(cfg.experts_per_token):
            dense_r[ti, int(idx[ti, j])] += float(weights[ti, j])
    err = float(np.abs(np.asarray(via_spmm) - dense_r @ np.asarray(summaries)).max())
    print(f"combine via EIE-like SpMM kernel: max err = {err:.2e}")
    assert err < 1e-4

    # What would AESPA schedule for this dispatch matmul?
    w = Workload("moe_dispatch", "LM", t, cfg.n_experts, cfg.d_model,
                 density, 1.0)
    s = schedule_single_kernel(dse.aespa_equal4(), w)
    classes = sorted({part.cls.value for part in s.partitions})
    print(f"AESPA single-kernel schedule for the dispatch: {classes}, "
          f"est runtime {s.report.runtime_s * 1e9:.0f} ns")


if __name__ == "__main__":
    main()
