"""The control: the reference put in the program's place at a lower precision
must come out not correct under the committed limits, where the program's own
run comes out correct.

On the chip, at the cells' sizes, ``bench/calibrate.py`` prints each control's
verdict (``PERF.md`` gives the readings): the fits and serving cells are held
against ``Precision.HIGH`` (three bf16 passes), the next precision below the
configurations' float32 at ``HIGHEST``; the async cell, whose one-row
matmuls the chip runs in float32 at any precision, against one bf16 pass.
Here, at a size a CPU holds, the three passes are written out, and a barely
trained small map's large distances hide their rounding: they read an order
of magnitude under the chip's native ``HIGH`` at the cells' sizes and pass
the limits. So on the CPU every cell's control is one bf16 pass, the next
tier down.
"""
from __future__ import annotations

import pytest

from conftest import tiny_cell
from harness import cell as cell_lib, spec

#: (workload, side, dim, train rows, control precision, the number that
#: separates)
CASES = [("mnist40-fit-fused", 8, 784, 2048, "bf16", "q2_mae"),
         ("mnist40-async-exp-stream", 6, 256, 1024, "bf16", "q2_mae"),
         ("mnist40-serve-open", 8, 784, 2048, "bf16", "qe_gap")]


@pytest.mark.parametrize("workload,side,dim,train,precision,number", CASES,
                         ids=[c[0] for c in CASES])
def test_program_passes_and_control_reads_higher(workload, side, dim, train,
                                                 precision, number,
                                                 cpu_devices):
    cell = tiny_cell(workload, side, dim, train)
    cell["config"]["afm"]["i_max"] = 40 * side * side
    drv = spec.driver(cell["traffic"]["kind"])
    run = cell_lib.Run(cell, 3_000_000_123, cpu_devices)
    drv.setup(run)
    drv.window(run, 1.0)
    drv.release(run)
    program = drv.check(run)
    ok, checks = cell_lib.judge(program, cell["limits"])
    assert ok, checks
    control = drv.stand_in(run, precision, "none")
    ok, checks = cell_lib.judge(control, cell["limits"])
    assert not ok, checks
    assert checks[number]["value"] > checks[number]["limit"], checks
    assert control[number] >= 3 * program[number], (control, program)
