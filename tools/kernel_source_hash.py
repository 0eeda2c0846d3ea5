"""Content hash of the kernel sources the compiled selftest proves.

A ``tests_tpu/`` result is evidence about the kernel code AS IT WAS
when the tests ran on the chip; quoting it after an ``ops/`` edit would
pass stale evidence off as current. This hash identifies the sources a
recorded result was about. (Its consumer, bench.py's banked-selftest
reuse, is gone; removing this tool with its tests is ROADMAP queue 3
item 4.)

Scope: every ``.py`` under ``tests_tpu/`` (the parity assertions),
``tensorflow_examples_tpu/ops/`` (the kernels they compile), and
``tensorflow_examples_tpu/parallel/`` (round 5: the gmm parity nodes
compile through parallel/moe.py's dispatch — a gmm-tiling edit there
must stale them, and ring/ulysses sit in the same boat for the lse
nodes). Hash is over (relative path, content) pairs in sorted order,
so renames and adds/removes change it too.

Usage: ``python tools/kernel_source_hash.py`` prints the hash.
"""

import hashlib
import os


def kernel_source_hash(repo_root: "str | None" = None) -> str:
    root = repo_root or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )
    h = hashlib.sha256()
    for sub in (
        "tests_tpu",
        os.path.join("tensorflow_examples_tpu", "ops"),
        os.path.join("tensorflow_examples_tpu", "parallel"),
    ):
        base = os.path.join(root, sub)
        files = []
        for dirpath, _dirnames, filenames in os.walk(base):
            files.extend(
                os.path.join(dirpath, f)
                for f in filenames
                if f.endswith(".py")
            )
        for path in sorted(files):
            h.update(os.path.relpath(path, root).encode())
            h.update(b"\0")
            with open(path, "rb") as f:
                h.update(f.read())
            h.update(b"\0")
    return h.hexdigest()


if __name__ == "__main__":
    print(kernel_source_hash())
