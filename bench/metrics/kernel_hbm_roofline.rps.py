"""kernel_hbm_roofline.rps: the tuned bucket programs' share of the HBM
roofline, in %, in an open-loop serving cell (bench.lib.layer.
serve_roofline; the floor bytes count no index bytes).  Moves spmv_rps."""
from bench.lib.layer import serve_roofline as read  # noqa: F401
