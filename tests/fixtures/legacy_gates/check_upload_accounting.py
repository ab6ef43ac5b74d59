#!/usr/bin/env python
"""Upload-accounting gate: no raw host→device transfers in models/ or ops/.

Every host→device upload a model or op makes must ride the accounted
stager in flink_ml_tpu/parallel/prefetch.py (`stage_to_device` /
`stage_from_callback`) — that is what keeps the `h2d.bytes` / `h2d.count`
counters (and the BENCH `h2dBytes` field, and the inputPipeline entry's
zero-upload-epochs claim) an exhaustive answer to "what bytes were
uploaded host→device". A raw `jax.device_put` in a model would execute fine
and silently disappear from the accounting, so this gate fails the build
instead — the upload-side mirror of `check_collective_accounting.py`. It
scans every .py file under flink_ml_tpu/models and flink_ml_tpu/ops for
direct calls to the jax transfer entry points (comments and string
literals are stripped via tokenize, so docstrings that *mention*
device_put stay legal).

Implicit uploads (`jnp.asarray(host_array)` feeding a jitted kernel, jit
argument transfer) are invisible to source scanning and intentionally out
of scope — the gate covers the explicit bulk-transfer surface, where
bypassing the stager is a one-line mistake; the bulk data paths all stage
explicitly so their shards land pre-placed.

Run directly (exit code 1 on violations) or via
tests/test_upload_accounting.py, which keeps the gate in tier-1.
"""

from __future__ import annotations

import io
import os
import re
import sys
import tokenize
from typing import List, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCANNED_DIRS = ("flink_ml_tpu/models", "flink_ml_tpu/ops")

# the explicit host->device transfer entry points the stager wraps
_PRIMITIVES = (
    "device_put",
    "device_put_sharded",
    "device_put_replicated",
    "make_array_from_callback",
    "make_array_from_single_device_arrays",
)
_PATTERN = re.compile(
    r"\bjax\s*\.\s*(" + "|".join(_PRIMITIVES) + r")\s*\("
)


def _code_only(source: str) -> str:
    """Source with comments and string/docstring tokens blanked (newlines
    kept, so reported line numbers stay true)."""
    out = []
    try:
        tokens = list(tokenize.generate_tokens(io.StringIO(source).readline))
    except tokenize.TokenError:
        return source
    lines = source.splitlines(keepends=True)
    drop = []  # (srow, scol, erow, ecol) spans to blank
    for tok in tokens:
        if tok.type in (tokenize.COMMENT, tokenize.STRING):
            drop.append((tok.start, tok.end))
    for line_no, line in enumerate(lines, start=1):
        buf = list(line)
        for (srow, scol), (erow, ecol) in drop:
            if srow <= line_no <= erow:
                lo = scol if line_no == srow else 0
                hi = ecol if line_no == erow else len(buf)
                for i in range(lo, min(hi, len(buf))):
                    if buf[i] not in "\r\n":
                        buf[i] = " "
        out.append("".join(buf))
    return "".join(out)


def find_violations() -> List[Tuple[str, int, str]]:
    """(path, line, primitive) for every raw transfer call in scope."""
    violations = []
    for rel_dir in SCANNED_DIRS:
        base = os.path.join(ROOT, rel_dir)
        for dirpath, _, filenames in os.walk(base):
            for fname in sorted(filenames):
                if not fname.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fname)
                with open(path) as f:
                    code = _code_only(f.read())
                for i, line in enumerate(code.splitlines(), start=1):
                    for match in _PATTERN.finditer(line):
                        violations.append(
                            (os.path.relpath(path, ROOT), i, match.group(1))
                        )
    return violations


def main() -> int:
    violations = find_violations()
    if violations:
        print(
            f"upload accounting: {len(violations)} raw host->device transfer "
            "call(s) bypass the accounted stager "
            "(use flink_ml_tpu.parallel.prefetch.stage_to_device instead):"
        )
        for path, line, prim in violations:
            print(f"  {path}:{line}: jax.{prim}(...)")
        return 1
    print(
        "upload accounting: no raw host->device transfers in "
        + " or ".join(SCANNED_DIRS)
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
