from repro.roofline.attribution import (
    attribute,
    model_packed_costs,
    profile_packed_tree,
    rank_hlo_hotspots,
    render_report,
)
from repro.roofline.hlo_costs import (
    Costs,
    analyze_hlo,
    entry_name,
    instr_bytes,
    parse_hlo,
    shape_bytes,
    trip_count,
    trip_multipliers,
    while_parts,
)
from repro.roofline.hw import (
    CHIP_PEAKS,
    HBM_BW,
    ICI_BW,
    PEAK_FLOPS_BF16,
    RooflineTerms,
    chip_peaks,
    model_flops_infer,
    model_flops_train,
    roofline_terms,
)
