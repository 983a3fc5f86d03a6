"""engine.padded_share: the engine's zero-padded columns, in % of the columns
it dispatched over the window, in an open-loop serving cell.  Moves
spmv_rps: a padded column is device time that serves no request."""
from bench.lib.layer import padded_share as read  # noqa: F401
