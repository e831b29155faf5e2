"""R7 — storage-layer renames must go through the blessed commit
helper.

The crash-consistency PR centralizes every commit-path rename in
``minio_tpu/storage/xl.py::commit_replace`` — the one choke point
where the ``storage fsync=on`` durability policy (fsync source +
destination parent dir) is applied, and where any future
commit-ordering change lands once instead of being hand-synced across
N call sites. A raw ``os.replace``/``os.rename`` added anywhere under
``minio_tpu/storage/`` silently bypasses that policy: the write LOOKS
committed but never fsyncs, which is precisely the class of bug that
only shows up as lost data after a power cut — undetectable by every
test that doesn't yank the cord.

The helper's own ``os.replace`` carries a justified suppression (the
waiver doubles as the pointer to the policy seam). ``shutil.move`` and
friends are not flagged — they do not appear on commit paths here, and
widening the net to every file op would bury the signal.

There is a SECOND blessed site: the storage layer's native lane
(``minio_tpu/native/fsops.cc``, the system calls of ``append_file`` /
``rename_data`` batched into GIL-free calls) renames in C, where no
Python helper can stand in the way. Its one ``rename(2)`` lives in
``commit_rename(src, dst)``; the lane is not taken with ``storage
fsync=on`` (``xl._native_lib``), so the helper holds no policy, only
the one place to look. The rule reads the C++ sources beside the
native loader and flags a ``rename`` / ``renameat`` call in any other
function.
"""

from __future__ import annotations

import ast
import os
import re

from ..core import REPO, Finding, Rule, dotted_name

NATIVE_LOADER = "minio_tpu/native/__init__.py"
NATIVE_HELPER = "commit_rename"
_C_RENAME = re.compile(r"(?<![\w.>])(?:rename|renameat2?)\s*\(")
# A function definition in these sources starts at column 0 and keeps
# its name and opening parenthesis on that line.
_C_FUNC = re.compile(r"^[A-Za-z_][\w:<>*&\s]*?\b(\w+)\s*\(")


def check_native_source(relpath: str, text: str) -> list[Finding]:
    """R7 over one C++ source of the native library."""
    out: list[Finding] = []
    func = ""
    for n, line in enumerate(text.splitlines(), 1):
        code = line.split("//", 1)[0]
        m = _C_FUNC.match(code)
        if m and m.group(1) not in ("if", "for", "while", "switch"):
            func = m.group(1)
            continue
        if _C_RENAME.search(code) and func != NATIVE_HELPER:
            out.append(Finding("R7", relpath, n, (
                f"raw rename in {func or 'file scope'}() of the native "
                f"storage lane — route it through {NATIVE_HELPER}(src, "
                "dst), the second blessed commit-path rename (the "
                "first is storage/xl.py commit_replace)")))
    return out


class CommitReplaceRule(Rule):
    id = "R7"
    title = ("os.replace/os.rename in minio_tpu/storage/ must route "
             "through the blessed commit helper (xl.commit_replace); "
             "rename(2) in minio_tpu/native/*.cc through commit_rename")

    def applies(self, ctx) -> bool:
        return (ctx.relpath.startswith("minio_tpu/storage/")
                or ctx.relpath == NATIVE_LOADER)

    def check(self, ctx) -> list[Finding]:
        if ctx.relpath != NATIVE_LOADER:
            return super().check(ctx)
        # The loader's own renames move a built library, not an object:
        # what it stands for here is the C++ it builds, read as text.
        findings: list[Finding] = []
        native_dir = os.path.dirname(ctx.path)
        if not os.path.isdir(native_dir):
            return findings
        for name in sorted(os.listdir(native_dir)):
            if name.endswith(".cc"):
                full = os.path.join(native_dir, name)
                with open(full, encoding="utf-8") as f:
                    findings.extend(check_native_source(
                        os.path.relpath(full, REPO).replace(os.sep, "/"),
                        f.read()))
        return findings

    def visit_Call(self, node: ast.Call) -> None:
        name = dotted_name(node.func)
        if name in ("os.replace", "os.rename"):
            self.flag(node, (
                f"raw {name} on a storage path — route the rename "
                "through storage/xl.py commit_replace so the fsync "
                "commit policy (and future ordering changes) apply; "
                "a justified '# mtpu-lint: disable=R7' waiver is the "
                "escape hatch for genuinely non-commit renames"))
        self.generic_visit(node)
