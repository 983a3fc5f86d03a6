"""cg.iterations: the mean number of CG iterations per solve in the window,
from SolverResult.iterations.  Moves cg_solve_s: a solve's time is its
iterations times the time of one fused step."""
from bench.lib.layer import cg_iterations as read  # noqa: F401
