"""Roofline term derivation (EXPERIMENTS.md §Roofline).

  compute term    = HLO_FLOPs / (chips x peak_FLOP/s)
  memory term     = HLO/analytic bytes / (chips x HBM_bw)
  collective term = collective_bytes / (chips x link_bw)

Sources:
  * FLOPs + collective bytes — trip-count-aware HLO walk
    (repro.utils.hlo_cost; XLA's cost_analysis counts scan bodies once, so
    it is recorded raw but NOT used for the terms).
  * memory term — analytic traffic model below. Fusion makes exact HBM
    traffic unknowable from HLO text; the analytic model uses exact pytree
    byte sizes (params / optimizer state / KV cache from eval_shape) with
    documented traffic multipliers, the standard roofline practice.

Traffic model (global bytes per step):
  train   : 3x params (fwd + bwd + remat re-read) + 2x params (grad write +
            param write) + 2x opt state (read+write)
            + 8x tokens x d_model x n_layers x act_bytes  (layer carries:
              fwd write/read + remat write/read, x2 residual streams)
  prefill : 1x params + 4x tokens x d_model x n_layers + cache write
  decode  : 1x params (every weight read once per token)
            + 1x KV-cache read + small cache write
"""
from __future__ import annotations

from repro.launch.mesh import chip_peaks


def analytic_memory_bytes(mode: str, *, params_bytes: float,
                          opt_bytes: float = 0.0, cache_bytes: float = 0.0,
                          tokens: float = 0.0, d_model: int = 0,
                          n_layers: int = 0, act_bytes: int = 2) -> float:
    act = 8.0 * tokens * d_model * n_layers * act_bytes
    if mode == "train":
        return 5.0 * params_bytes + 2.0 * opt_bytes + act
    if mode == "prefill":
        return params_bytes + act / 2.0 + cache_bytes
    # decode
    return params_bytes + cache_bytes + 2.0 * tokens * d_model * n_layers * act_bytes


def roofline_terms(n_chips: int, flops_global: float, mem_bytes_global: float,
                   coll_bytes_global: float, *, device_kind: str) -> dict:
    """Roofline seconds of one step on ``n_chips`` chips of ``device_kind``
    (peaks from ``launch.mesh.CHIP_PEAKS``; an unknown kind raises)."""
    peaks = chip_peaks(device_kind)
    compute_s = flops_global / (n_chips * peaks["peak_flops_bf16"])
    memory_s = mem_bytes_global / (n_chips * peaks["hbm_bw"])
    collective_s = coll_bytes_global / (n_chips * peaks["ici_bw_per_link"])
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dom = max(terms, key=lambda k: terms[k])
    return {**terms, "dominant": dom,
            "roofline_step_s": max(compute_s, memory_s, collective_s)}
