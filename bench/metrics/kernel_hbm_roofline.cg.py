"""kernel_hbm_roofline.cg: the fused CG program's share of the HBM roofline,
in %, over the traced window (bench.lib.layer.cg_roofline; the floor bytes
count no index bytes).  Moves cg_solve_s."""
from bench.lib.layer import cg_roofline as read  # noqa: F401
