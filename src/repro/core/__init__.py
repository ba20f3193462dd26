"""Core library: the paper's contribution (BLCO format + mode-agnostic MTTKRP
+ OOM streaming + CP-ALS) and its baselines."""
from .tensor import (SparseTensor, random_tensor, random_tensor_exact, from_coo,
                     load_tns, paper_like, frostt_like)
from .blco import BLCOTensor, build_blco, decode_coords, format_bytes
from .mttkrp import (mttkrp, mttkrp_per_launch, choose_resolution,
                     clear_launch_cache, launch_cache_for,
                     mttkrp_dense_oracle, khatri_rao, DeviceBLCO)
from .launches import LaunchCache, launch_cache_bytes, stacked_mttkrp
from .counters import dispatch_count
from .baselines import (COOFormat, coo_mttkrp, FCOOFormat, fcoo_mttkrp,
                        CSFFormat, csf_mttkrp)
from .cp_als import (cp_als, cp_als_init, cp_als_step, as_mttkrp_fn, CPResult,
                     CPState, init_factors, reconstruct_dense)
from .streaming import (EngineStats, LaunchChunks, OOMExecutor,
                        ReservationSpec, StreamStats)
from .embed_grad import embedding_lookup

__all__ = [
    "SparseTensor", "random_tensor", "random_tensor_exact", "from_coo",
    "load_tns", "paper_like", "frostt_like",
    "BLCOTensor", "build_blco", "decode_coords", "format_bytes",
    "mttkrp", "mttkrp_per_launch", "choose_resolution",
    "clear_launch_cache", "launch_cache_for",
    "mttkrp_dense_oracle", "khatri_rao", "DeviceBLCO",
    "LaunchCache", "launch_cache_bytes", "stacked_mttkrp", "dispatch_count",
    "COOFormat", "coo_mttkrp", "FCOOFormat", "fcoo_mttkrp",
    "CSFFormat", "csf_mttkrp",
    "cp_als", "cp_als_init", "cp_als_step", "as_mttkrp_fn", "CPResult",
    "CPState", "init_factors", "reconstruct_dense",
    "EngineStats", "LaunchChunks", "OOMExecutor", "ReservationSpec",
    "StreamStats",
    "embedding_lookup",
]
