#!/usr/bin/env python3
"""The control of ``lsm_kirchhoff_line.cgls_shots32``'s comparison: run the
cell with a deliberately wrong plain solve standing in for the program,

    python3 chipbench/scratch/lsm_line_control.py bf16 --workload \\
        lsm_kirchhoff_line.cgls_shots32 --seed 7 --seconds 10 --trace 0

(``bf16``: every sprayed and gathered product rounded to bfloat16 — the
builder's ``CONTROLS``, ``builders/lsm.py``'s — in the reference sharded
over the chips). Everything else is ``chipbench/run.py``: the same
builder, loop, limits and verdict. The loop's own comparison
(``closed_vstack.judge``) has to refuse it — exit code 1 with the
reading beside ``rel_tol`` on stderr; ``--rehearse`` does the same tiny
on four virtual CPU devices (``chipbench/tests/test_lsm_line_cell.py``).
``scratch/lsm_control.py`` is the one-chip cell's.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    from chipbench import run
    from chipbench.builders import lsm_line
    kind, rest = argv[0], argv[1:]
    build = lsm_line.build

    def with_control(*args, **kw):
        dep = build(*args, **kw)
        dep.stand_in = dep.control(kind)
        run.log(f"CONTROL: the plain solve {lsm_line.CONTROLS[kind]} "
                "stands in for the program")
        return dep

    lsm_line.build = with_control
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
