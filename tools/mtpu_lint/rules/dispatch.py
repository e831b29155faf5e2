"""R9 — backend-selection policy lives in ``ops/autotune.py``.

PR 12 replaced the hardwired device-first codec dispatch policy (a
fixed ``TPU_MIN_BYTES`` crossover plus device-present checks scattered
through ops/ and the codec) with the measured per-(kernel, bucket)
throughput planner.  This rule keeps the policy from leaking back out:
in the dispatch-decision modules (``minio_tpu/ops/`` and
``minio_tpu/erasure/codec.py``, excluding the planner itself), it
flags

- comparisons against size-threshold constants (names matching
  ``*MIN_BYTES`` / ``*THRESHOLD`` / large byte literals compared to a
  size-ish operand) — a hardwired crossover is wrong on every box but
  the one it was tuned on, and
- kernprof lane-name string literals (``"device"`` / ``"native"`` /
  ``"xla-cpu"`` / ``"host"``) in comparisons — lane identity belongs
  to the planner and the state machine, not inline policy.  The
  user-facing codec pins (``backend == "tpu" | "cpu"``) are NOT lane
  names and stay legal.

Justified waivers (``# mtpu-lint: disable=R9 -- why``) are the escape
hatch, as for every rule.
"""

from __future__ import annotations

import ast
import re

from ..core import Rule, terminal_name

_LANE_LITERALS = {"device", "native", "xla-cpu", "host"}
_THRESH_NAME = re.compile(r"(MIN_BYTES|THRESHOLD|_MIN$)", re.I)
_SIZE_NAME = re.compile(r"(bytes|size|len)", re.I)
# Byte literals this large in a comparison smell like a dispatch
# crossover, not a loop bound.
_BYTES_FLOOR = 64 * 1024


class DispatchPolicyRule(Rule):
    id = "R9"
    title = ("backend-selection thresholds and lane literals belong in "
             "ops/autotune.py")

    PATHS = ("minio_tpu/ops/", "minio_tpu/erasure/codec.py")
    EXEMPT = ("minio_tpu/ops/autotune.py",)

    def applies(self, ctx) -> bool:
        rel = ctx.relpath
        if rel in self.EXEMPT:
            return False
        return rel == "minio_tpu/erasure/codec.py" or rel.startswith(
            "minio_tpu/ops/")

    def visit_Compare(self, node: ast.Compare) -> None:
        operands = [node.left, *node.comparators]
        for op in operands:
            if isinstance(op, ast.Constant) \
                    and isinstance(op.value, str) \
                    and op.value in _LANE_LITERALS:
                self.flag(node, (
                    f"kernprof lane literal {op.value!r} in a dispatch "
                    "comparison — lane selection belongs to "
                    "ops/autotune.py (import the kernprof constant if "
                    "you only need identity)"))
                break
        names = [terminal_name(op) for op in operands]
        if any(n and _THRESH_NAME.search(n) for n in names):
            self.flag(node, (
                "hardwired backend-selection size threshold in a "
                "dispatch decision — the measured plan in "
                "ops/autotune.py owns the crossover"))
            return
        # An int literal >= 64KiB compared against a size-ish name is
        # the same threshold with the constant inlined.
        has_size_name = any(n and _SIZE_NAME.search(n) for n in names)
        big_literal = any(
            isinstance(op, ast.Constant) and isinstance(op.value, int)
            and not isinstance(op.value, bool)
            and op.value >= _BYTES_FLOOR for op in operands)
        if has_size_name and big_literal:
            self.flag(node, (
                "inline byte-size crossover in a dispatch decision — "
                "the measured plan in ops/autotune.py owns the "
                "crossover"))
        self.generic_visit(node)
