"""device_idle.rps: % of the traced window with no operation on the device,
in an open-loop serving cell.  Moves spmv_rps: idle device time is capacity
lost to the host."""
from bench.lib.layer import idle_share as read  # noqa: F401
