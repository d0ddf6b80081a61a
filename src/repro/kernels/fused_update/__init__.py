from .ops import LANES, fused_plane_stage, make_plane_stage

__all__ = ["LANES", "fused_plane_stage", "make_plane_stage"]
