"""device_idle.serve: % of the traced window with no operation on the device,
in a closed-loop serving cell.  Moves spmv_p95_ms: while the device idles,
the caller's next request waits on the host."""
from bench.lib.layer import idle_share as read  # noqa: F401
