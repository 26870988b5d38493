"""repro.core — the paper's contribution: space-filling-curve machinery.

Modules:
  hilbert       Mealy-automaton H(i,j) / H^-1(h)            (paper §3)
  lindenmayer   CFG + non-recursive Fig.5 generators        (paper §4-5)
  zorder        Z-order / Gray-code baselines               (paper §2)
  peano         3-adic Peano curve baseline                 (paper §2.1)
  fur           overlay-grid curves for arbitrary n×m       (paper §6.1)
  fgf           jump-over walker for general regions        (paper §6.2)
  nano          nano-programs (packed curve fragments)      (paper §6.3)
  hilbert_nd    d-dimensional Hilbert/Z-order/Gray codecs   (beyond-paper)
  fgf_nd        d-dimensional jump-over walker              (beyond-paper)
  curve         SpaceFillingCurve abstraction + registry    (beyond-paper)
  curves_nd     table-driven curve algebras (harmonious,
                cyclic) + verification oracles              (beyond-paper)
  schedule      tile-schedule factory + traffic models      (TPU adaptation)
  program       CurveProgram declarations + VMEM budget +
                curve-range partitioning                    (execution layer)
  jax_hilbert   device-side vectorised codec                (TPU adaptation)
  neighbors     curve-neighbour range calculus (halo
                exchange for the sharded apps)              (beyond-paper)
"""
from .curve import (
    SpaceFillingCurve,
    available_curves,
    curve_supports,
    get_curve,
    register,
)
from .fgf import (
    EMPTY,
    FULL,
    PARTIAL,
    band_classifier,
    causal_classifier,
    cover_order,
    fgf_path,
    fgf_rect,
    fgf_triangle,
    intersect,
    predicate_classifier,
    rect_classifier,
    triangle_classifier,
)
from .curves_nd import (
    CurveAlgebra,
    TableCurveAlgebra,
    algebra_names,
    facet_consistency_score,
    get_algebra,
    register_algebra,
    table_curve_oracle,
    verify_table_curve,
)
from .fgf_nd import (
    BandRegion,
    BoxRegion,
    IntersectRegion,
    PredicateRegion,
    TriangleRegion,
    curve_jump_path_nd,
    fgf_box_nd,
    fgf_path_nd,
    fgf_triangle_nd,
    hilbert_jump_path_nd,
)
from .fur import fur_is_unit_step, fur_path
from .hilbert import (
    canonical_start_state,
    decode_from_state,
    hilbert_decode,
    hilbert_decode_t,
    hilbert_encode,
    hilbert_encode_t,
    hilbert_path,
)
from .hilbert_nd import (
    canonical_nbits,
    canonical_start_state_nd,
    child_corner_nd,
    child_state_nd,
    child_transforms_nd,
    clip_path_nd,
    decode_from_state_nd,
    gray_decode_nd,
    gray_encode_nd,
    gray_path_nd,
    hilbert_decode_nd,
    hilbert_decode_raw_nd,
    hilbert_encode_nd,
    hilbert_path_nd,
    identity_state_nd,
    zorder_decode_nd,
    zorder_encode_nd,
    zorder_path_nd,
)
from .jax_hilbert import (
    hilbert_decode_jax,
    hilbert_encode_jax,
    hilbert_encode_nd_jax,
    hilbert_sort_key,
    schedule_to_device,
    zorder_encode_jax,
)
from .lindenmayer import (
    hilbert_path_nonrecursive,
    hilbert_path_recursive,
    hilbert_path_vectorised,
    lindenmayer_nonrecursive,
)
from .neighbors import (
    curve_range_boxes,
    halo_ranges,
    halo_ranges_oracle,
    neighbor_tile_mask,
)
from .peano import peano_decode, peano_encode, peano_path
from .program import (
    CurveProgram,
    VMEM_BUDGET_DEFAULT,
    curve_partition,
    fits_vmem,
    fused_fits,
    get_vmem_budget,
    set_vmem_budget,
)
from .schedule import (
    CHOLESKY_PHASES,
    CURVES,
    FW_PHASES,
    KMEANS_PHASES,
    SCHEDULE_KINDS,
    ScheduleChoice,
    as_choice,
    build_schedule,
    kmeans_schedule,
    kmeans_schedule_device,
    lru_misses,
    matmul_traffic_bytes,
    matmul_traffic_bytes_3d,
    min_revisit_gap,
    miss_counts,
    miss_curve,
    operand_reloads,
    operand_reloads_nd,
    pair_stream,
    phase_barrier_gaps,
    phase_barriers,
    phased_schedule,
    phased_schedule_device,
    register_schedule_cache,
    reuse_distances,
    schedule_cache_clear,
    schedule_hilbert_values,
    tile_schedule,
    tile_schedule_device,
    tile_schedule_nd,
    triangle_schedule,
    triangle_schedule_nd,
)
from .zorder import (
    gray_decode,
    gray_encode,
    gray_path,
    zorder_decode,
    zorder_encode,
    zorder_path,
)

__all__ = [k for k in dir() if not k.startswith("_")]
