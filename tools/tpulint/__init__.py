"""tpulint: AST-based invariant linter for the TPU columnar stack.

The reference repo enforces its invariants at compile time (C++ types,
JNI signature checks); this pure-Python reproduction has no compiler to
lean on, so the whole-program invariants the stack relies on — host/
device boundary discipline, sentinel safety, the regex padding byte,
dtype width, validity-mask derivation — are enforced here mechanically
over the stdlib ``ast``. No third-party dependencies, files are parsed
and never imported.

Two tiers of rules share one CLI and one suppression model: twenty-two
per-file AST rules (``tools/tpulint/rules.py``) and three whole-program
concurrency rules (``tools/tpulint/concurrency.py`` — lock-order-cycle,
blocking-call-under-lock, unguarded-shared-write) that run on the
``tools/tpulint/flows.py`` interprocedural engine: one parse of the
whole corpus, a module-level call graph, a lock registry, and held-set
propagation through ``with`` blocks and intra-package calls.

Entry points:
  * CLI:      ``python -m tools.tpulint spark_rapids_jni_tpu``
              (``--format json`` for machine-readable findings,
              ``--lock-graph`` to dump the lock-order graph, exit 1 if
              cyclic)
  * pytest:   ``tests/test_tpulint.py`` (whole-package sweep + seeded
              violation fixtures per rule)
  * CI:       ``ci/lint.sh`` from ``ci/premerge-build.sh``

Suppression: ``# tpulint: disable=<rule>[,<rule>...]`` on the offending
line (or a comment line directly above), and ``tools/tpulint/
baseline.txt`` for pre-existing findings (regenerate with
``python -m tools.tpulint --write-baseline <paths>``).
"""

from tools.tpulint.concurrency import (  # noqa: F401
    PROGRAM_RULE_NAMES,
    PROGRAM_RULES,
    lock_graph_report,
)
from tools.tpulint.engine import (  # noqa: F401
    Finding,
    format_finding,
    lint_paths,
    lint_source,
    load_baseline,
    write_baseline,
)
from tools.tpulint.rules import RULES  # noqa: F401
