#!/usr/bin/env python3
"""The control of ``lsm_kirchhoff.cgls_shots8``'s comparison: run the cell with
a deliberately wrong plain solve standing in for the program,

    python3 chipbench/scratch/lsm_control.py bf16 --workload \\
        lsm_kirchhoff.cgls_shots8 --seed 7 --seconds 10 --trace 0

(``bf16``: every sprayed and gathered product rounded to bfloat16, what
a one-pass MXU contraction of the model against a one-hot would give —
the builder's ``CONTROLS``). Everything else is
``chipbench/run.py``: the same builder, loop, limits and verdict. The
loop's own comparison (``closed_vstack.judge``) has to refuse it —
exit code 1 with the reading beside ``rel_tol`` on stderr;
``--rehearse`` does the same tiny on the CPU
(``chipbench/tests/test_lsm_cell.py``).
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    from chipbench import run
    from chipbench.builders import lsm
    kind, rest = argv[0], argv[1:]
    build = lsm.build

    def with_control(*args, **kw):
        dep = build(*args, **kw)
        dep.stand_in = dep.control(kind)
        run.log(f"CONTROL: the plain solve {lsm.CONTROLS[kind]} "
                "stands in for the program")
        return dep

    lsm.build = with_control
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
