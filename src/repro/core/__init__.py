"""TriADA core — the paper's algorithm layer (§2–§6).

Trilinear matrix-by-tensor multiply-add: the staged/outer-product GEMT
(§2–§3), DXT coefficient matrices (§2.2), ESOP sparse skipping (§6), the
cell-grid device simulator (§5), Tucker compression (§2.3), and the
distributed TriADA schedule (§4–§5, Eq. 7).  The paper-section→module map
lives in ``docs/architecture.md``; the distributed recipes in
``docs/distributed.md``.
"""
from .gemt import (PAREN_ORDERS, clear_coefficient_cache, dxt3d, gemt3,
                   gemt3_outer, gemt3_planned, macs, mode_product, time_steps)
from .transforms import (TRANSFORM_KINDS, coefficient_matrix, dct2_matrix,
                         dft_matrix, dht_matrix, dwht_matrix,
                         inverse_coefficient_matrix)
from .esop import (EsopStats, accumulation_error, block_nonzero_mask,
                   energy_joules, esop_gemt3, esop_stage_counts, prune,
                   sparsity)
from .cellsim import TriadaCellGrid, simulate_dxt3
from .tucker import hosvd, tucker_compress, tucker_expand, tucker_roundtrip_error
from .distributed import gemt3_auto, gemt3_shardmap, tensor_spec
from .layers import (apply_dxt3d_layer, apply_triada_dense,
                     apply_triada_mixer, init_dxt3d_layer, init_triada_dense,
                     make_mixer_coeffs)

__all__ = [
    "PAREN_ORDERS", "clear_coefficient_cache", "dxt3d", "gemt3",
    "gemt3_outer", "gemt3_planned", "macs", "mode_product", "time_steps",
    "TRANSFORM_KINDS", "coefficient_matrix", "dct2_matrix", "dft_matrix",
    "dht_matrix", "dwht_matrix", "inverse_coefficient_matrix",
    "EsopStats", "accumulation_error", "block_nonzero_mask", "energy_joules",
    "esop_gemt3", "esop_stage_counts", "prune", "sparsity",
    "TriadaCellGrid", "simulate_dxt3",
    "hosvd", "tucker_compress", "tucker_expand", "tucker_roundtrip_error",
    "gemt3_auto", "gemt3_shardmap", "tensor_spec",
    "apply_dxt3d_layer", "apply_triada_dense", "apply_triada_mixer",
    "init_dxt3d_layer", "init_triada_dense", "make_mixer_coeffs",
]
