# The paper's primary contribution: the GFID dataflow (gfid.py), its analytic
# performance model (analytics.py, Eqs 8-18) and the mode table (modes.py).
# The multi-mode engine itself lives in `repro.engine` (plan-based,
# functional).
from repro.core.modes import Mode, fc_mode, paper_mode, pes_per_tile  # noqa: F401
