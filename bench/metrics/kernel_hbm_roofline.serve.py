"""kernel_hbm_roofline.serve: the tuned bucket programs' share of the HBM
roofline, in %, in a closed-loop serving cell (bench.lib.layer.
serve_roofline; the floor bytes count no index bytes).  Moves spmv_p95_ms."""
from bench.lib.layer import serve_roofline as read  # noqa: F401
