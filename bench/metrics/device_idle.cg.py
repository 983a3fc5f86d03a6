"""device_idle.cg: % of the traced window with no operation on the device, in
a solver cell.  Moves cg_solve_s: between solves the device waits on the
host."""
from bench.lib.layer import idle_share as read  # noqa: F401
