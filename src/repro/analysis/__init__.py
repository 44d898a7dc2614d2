"""Static analysis over the repo's own traced jaxprs and compiled HLO.

The PR gate: `launch/gnn_lint.py` builds one representative program per
(entry point x model x backend x sync x codec) cell, runs every registered
rule over them and emits a machine-readable JSON report — exiting non-zero
on any error-level finding. The pieces:

  hlo.py        text-level HLO analysis (collective payload bytes per op
                kind under the output-shape convention, replica groups,
                scatter/convert inventory, input_output_alias)
  jaxpr.py      recursive jaxpr walking (primitive census, narrowing
                converts) across cond/scan/jit/pallas_call sub-jaxprs
  programs.py   the analyzed-program grid + seeded violations
  rules.py      the rule registry (no-scatter, dtype-policy,
                collective-budget, donation, retrace-guard) and Report
  deadcode.py   advisory dead-export sweep over src/tests/benchmarks
"""

from repro.analysis.hlo import (
    analyze_hlo,
    collective_bytes_from_hlo,
    input_output_aliases_from_hlo,
)
from repro.analysis.jaxpr import (
    convert_ops,
    count_primitives,
    iter_eqns,
    narrowing_converts,
    primitive_names,
)
from repro.analysis.programs import Program, build_programs, violation_program
from repro.analysis.rules import (
    RULES,
    Finding,
    Report,
    check_budget,
    check_narrowing,
    check_scatter,
    count_compiles,
    register_rule,
    run_rules,
)

__all__ = [
    "analyze_hlo",
    "collective_bytes_from_hlo",
    "input_output_aliases_from_hlo",
    "convert_ops",
    "count_primitives",
    "iter_eqns",
    "narrowing_converts",
    "primitive_names",
    "Program",
    "build_programs",
    "violation_program",
    "RULES",
    "Finding",
    "Report",
    "check_budget",
    "check_narrowing",
    "check_scatter",
    "count_compiles",
    "register_rule",
    "run_rules",
]
