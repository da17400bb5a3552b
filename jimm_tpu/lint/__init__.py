"""``jimm_tpu.lint`` — TPU-correctness static analyzer.

Layer 1 (always on) is pure-``ast`` rules JL002–JL016 and JL021 over
the source tree, plus the JL020 suppression-hygiene meta-rule. ``--concurrency``
builds a project-wide symbol table and call graph (``lint.graph``) and
runs the lock-discipline race detector (JL017–JL019), the tiered-
retrieval request-path IO rule (JL023), and interprocedural
escalations of JL006/JL008/JL013. ``--jaxpr`` is layer
1.5: abstract traces of registered entry points checked for promotion
drift, baked constants, and collective drift (JLT104–JLT106).
``--trace`` (layer 2) lowers entry points and asserts program-text
properties JLT101–JLT103. See ``docs/static_analysis.md`` for the rule
catalog and suppression syntax (``# jaxlint: disable=<rule> <why>``).
"""

from jimm_tpu.lint.core import ERROR, WARNING, Finding, lint_file, lint_paths

__all__ = ["ERROR", "WARNING", "Finding", "lint_file", "lint_paths"]
