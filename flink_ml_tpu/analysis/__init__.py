"""tpulint — AST-based static analysis for TPU dispatch hazards.

The train loop is host-dispatch-bound (one dispatch and one readback
around milliseconds of device work): the hazard classes that break that (hidden host syncs, per-call retraces,
unaccounted transfers, donated-buffer reuse, unstageable checkpoint tags)
are all *source-level* mistakes that a profiler only catches after a
regression ships. This package holds them statically instead:

- ``source``  — the shared source model (raw text, comment/string-stripped
  text, AST, ``# tpulint: disable=`` suppressions). The four legacy gate
  scripts' duplicated ``_code_only`` helpers live here now, once.
- ``engine``  — rule registry, project scanner, suppression resolution
  (an unused suppression is itself a finding), report formatting.
- ``rules/``  — one module per hazard family; each rule carries its own
  documentation (``id``, ``title``, ``rationale``, example).

Run via ``scripts/tpulint.py`` (or ``python -m pytest
tests/test_tpulint.py`` which keeps the zero-unsuppressed-findings
contract in tier-1). The catalogue is documented in
docs/static_analysis.md.
"""

from .engine import Finding, Project, all_rules, get_rule, run  # noqa: F401

__all__ = ["Finding", "Project", "all_rules", "get_rule", "run"]
