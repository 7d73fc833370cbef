"""tools/program_digests.py: the diff half, of two HLO texts and of two
digest files (the digests half lowers the 34 programs of test_spans'
fixture, and a builder runs it on two checkouts)."""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))

import program_digests  # noqa: E402


def test_diff_sees_through_a_renumbering_and_names_the_operation_that_changed(
    tmp_path, capsys
):
    a = tmp_path / "a.txt"
    b = tmp_path / "b.txt"
    a.write_text(
        "HloModule jit_f, entry_computation_layout={(u32[8]{0})->u32[8]{0}}\n"
        "ENTRY %main.3 (p.1: u32[8]) -> u32[8] {\n"
        "  %p.1 = u32[8]{0} parameter(0), metadata={op_name=\"x\"}\n"
        "  ROOT %add.2 = u32[8]{0} add(%p.1, %p.1), metadata={op_name=\"jit(f)/add\" stack_frame_id=4}\n"
        "}\n"
    )
    b.write_text(a.read_text().replace("%add.2", "%add.7").replace("%p.1", "%p.5")
                 .replace("stack_frame_id=4", "stack_frame_id=9"))
    assert program_digests.main(["diff", str(a), str(b)]) == 0
    capsys.readouterr()
    b.write_text(b.read_text().replace(" add(", " multiply("))
    assert program_digests.main(["diff", str(a), str(b)]) == 1
    out = capsys.readouterr().out
    assert f"only in {a}: 1" in out and f"only in {b}: 1" in out
    assert "multiply(%, %)" in out


def test_diff_of_two_digest_files_counts_the_equal_and_names_the_rest(
    tmp_path, capsys
):
    parent = tmp_path / "parent.json"
    tree = tmp_path / "tree.json"
    parent.write_text('{"step": "aa", "wave": "bb"}')
    tree.write_text('{"wave": "bb", "step": "aa"}')
    assert program_digests.main(["diff", str(parent), str(tree)]) == 0
    assert capsys.readouterr().out == "2 of 2 equal\n"
    tree.write_text('{"step": "aa", "wave": "cc", "sync": "dd"}')
    assert program_digests.main(["diff", str(parent), str(tree)]) == 1
    assert capsys.readouterr().out == (
        "1 of 3 equal\n"
        "  differs: sync (None -> dd)\n"
        "  differs: wave (bb -> cc)\n"
    )
