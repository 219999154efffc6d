"""Static contract verifier + repo invariant linter.

Two layers over one diagnostics model (stable rule ids, severities, JSON):

  * layer 1 (`verify`): pure-function verification of Program x
    EngineConfig pairs against the planning/tuning/sharding/precision
    contracts — wired into `engine.compile(verify=...)` and swept over
    every registered program by `python -m repro.analyze`;
  * layer 2 (`rules_ast`): custom `ast` rules over the `src/repro/`
    source tree enforcing structural invariants (engine routing, no
    mutable globals, guarded fault hooks, deterministic kernel bodies).

See README "Static analysis" for the rule catalog and allowlisting.
"""
from repro.analyze.diagnostics import (AnalyzeError, AnalyzeWarning,  # noqa: F401
                                       Diagnostic, Report, Rule, catalog,
                                       get_rule)
from repro.analyze.rules_ast import lint_file, lint_tree  # noqa: F401
from repro.analyze.rules_tile import doctor_cache  # noqa: F401
from repro.analyze.verify import verify_config, verify_program  # noqa: F401
