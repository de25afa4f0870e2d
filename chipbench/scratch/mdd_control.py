#!/usr/bin/env python3
"""The control of ``mdd_obc.cgls_nv16``'s comparison: run the cell with
a deliberately wrong plain solve standing in for the program,

    python3 chipbench/scratch/mdd_control.py bf16 --workload \\
        mdd_obc.cgls_nv16 --seed 7 --seconds 10 --trace 0

(``bf16``: both operands of every product of the Fredholm integral
rounded to bfloat16 — the builder's ``CONTROLS``). Everything else is
``chipbench/run.py``: the same builder, loop, limits and verdict. The
loop's own comparison (``closed_broadcast.judge``) has to refuse it —
exit code 1 with the reading beside ``rel_tol`` on stderr;
``--rehearse`` does the same tiny on the CPU
(``chipbench/tests/test_mdd_cell.py``).
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    from chipbench import run
    from chipbench.builders import mdd
    kind, rest = argv[0], argv[1:]
    build = mdd.build

    def with_control(*args, **kw):
        dep = build(*args, **kw)
        dep.stand_in = dep.control(kind)
        run.log(f"CONTROL: the plain solve {mdd.CONTROLS[kind]} "
                "stands in for the program")
        return dep

    mdd.build = with_control
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
