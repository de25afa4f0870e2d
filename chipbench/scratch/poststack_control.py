#!/usr/bin/env python3
"""The control of ``poststack_3d.reg_cgls``'s comparison: run the cell
with a deliberately wrong plain solve standing in for the program,

    python3 chipbench/scratch/poststack_control.py bf16 --workload \\
        poststack_3d.reg_cgls --seed 7 --seconds 10 --trace 0

(``bf16``: every product of the convolution rounded to bfloat16;
``taps31``: the wavelet cut to its central 31 taps — the builder's
``CONTROLS``). Everything else is ``chipbench/run.py``: the same
builder, loop, limits and verdict. The loop's own comparison
(``closed_stacked.judge``) has to refuse it — exit code 1 with the
reading beside ``corr_tol`` on stderr; ``--rehearse`` does the same tiny
on the CPU (``chipbench/tests/test_poststack_cell.py``).
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    from chipbench import run
    from chipbench.builders import poststack
    kind, rest = argv[0], argv[1:]
    build = poststack.build

    def with_control(*args, **kw):
        dep = build(*args, **kw)
        dep.stand_in = dep.control(kind)
        run.log(f"CONTROL: the plain solve {poststack.CONTROLS[kind]} "
                "stands in for the program")
        return dep

    poststack.build = with_control
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
