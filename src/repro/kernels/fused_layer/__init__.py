from .fused_layer import fused_ideal_layer, fused_quant_layer, fused_zmax
from .ops import (fused_gnn_layer, ideal_launch, ideal_layer_dmas,
                  prepare_sample, prepare_table)
from .ref import fused_layer_ref

__all__ = [
    "fused_ideal_layer", "fused_quant_layer", "fused_zmax",
    "fused_gnn_layer", "fused_layer_ref", "ideal_launch", "ideal_layer_dmas",
    "prepare_sample", "prepare_table",
]
