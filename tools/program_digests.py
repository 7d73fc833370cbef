"""Same programs, shown: digests of what a checkout lowers, and a diff of two.

How a PR shows that it changed no program (or exactly which):

    python tools/program_digests.py digests OUT.json [--root CHECKOUT]
    python tools/program_digests.py diff A B

``digests`` lowers every program of ``tests/test_spans.py``'s ``lowered``
fixture (both drivers' verbs at every observer level, the mesh programs,
the jaxprs of the whole-wave loops) from the checkout at ``--root`` (this
one by default; a ``git archive`` of the parent for the other side) and
writes a 16-hex sha256 of each text. Nothing is compiled or run.

``diff`` of two such files says how many of the programs are equal and
names the rest. Of two HLO texts (``lowered.as_text()`` or
``compiled.as_text()`` dumps of a program that differs) it is a multiset
diff with value names, metadata and stack frames stripped, so that a
renumbering does not hide the few operations that changed. Either way it
exits 1 if anything differs.
"""

from __future__ import annotations

import argparse
import collections
import hashlib
import json
import os
import re
import sys
from pathlib import Path


def digests(root: Path) -> dict:
    """name -> digest for every program of the checkout's fixture. Forces
    the CPU backend with the fixture's eight devices, so call it before
    anything in the process has touched jax."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    sys.path[:0] = [str(root), str(root / "tests")]
    import test_spans

    fixture = getattr(test_spans.lowered, "__wrapped__", None) or (
        test_spans.lowered.__pytest_wrapped__.obj
    )
    out = {}
    for name, program in fixture().items():
        text = str(program) if name.startswith(test_spans.JAXPR) else program.as_text()
        out[name] = hashlib.sha256(text.encode()).hexdigest()[:16]
    return out


def _digest_file(text: str):
    """The name -> digest table ``digests`` wrote, or None for an HLO text."""
    try:
        table = json.loads(text)
    except ValueError:
        return None
    return table if isinstance(table, dict) else None


def _operations(text: str) -> collections.Counter:
    """The operations of an HLO text as a multiset, names stripped."""
    out: collections.Counter = collections.Counter()
    for line in text.splitlines():
        line = line.strip()
        if " = " not in line or line.startswith(("HloModule", "ENTRY")):
            continue
        line = re.sub(r", metadata=\{[^}]*\}", "", line)
        line = re.sub(r"%[\w.\-]+", "%", line)
        line = re.sub(r"calls=%|to_apply=%|body=%|condition=%", "", line)
        line = re.sub(r"stack_frame_id=\d+", "", line)
        out[line] += 1
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    commands = parser.add_subparsers(dest="command", required=True)
    dig = commands.add_parser("digests", help="digest every program a checkout lowers")
    dig.add_argument("out", help="JSON file to write")
    dig.add_argument("--root", default=str(Path(__file__).resolve().parent.parent),
                     help="the checkout to lower from (default: this one)")
    diff = commands.add_parser("diff", help="compare two digest files, or two HLO texts")
    diff.add_argument("a")
    diff.add_argument("b")
    args = parser.parse_args(argv)

    if args.command == "digests":
        mine = digests(Path(args.root).resolve())
        with open(args.out, "w", encoding="utf-8") as handle:
            json.dump(mine, handle, indent=1, sort_keys=True)
        print(f"{len(mine)} programs -> {args.out}")
        return 0

    texts = [Path(path).read_text(encoding="utf-8") for path in (args.a, args.b)]
    tables = [_digest_file(text) for text in texts]
    if None not in tables:
        ours, theirs = tables
        names = sorted(set(ours) | set(theirs))
        differ = [name for name in names if ours.get(name) != theirs.get(name)]
        print(f"{len(names) - len(differ)} of {len(names)} equal")
        for name in differ:
            print(f"  differs: {name} ({ours.get(name)} -> {theirs.get(name)})")
        return 1 if differ else 0
    a, b = (_operations(text) for text in texts)
    for label, only in ((args.a, a - b), (args.b, b - a)):
        print(f"only in {label}: {sum(only.values())}")
        for line, count in only.most_common():
            print(f"  {count} {line[:230]}")
    return 1 if (a - b) or (b - a) else 0


if __name__ == "__main__":
    sys.exit(main())
