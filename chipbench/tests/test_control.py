"""``correct`` on the CPU at a small size: the program's numbers are
within the cell's limits, and the control (the reference at three
bfloat16 passes, one precision step below the configuration's) is not.
At the cells' own size the same comparison runs on the chip through
``harness/control.py``."""
import pytest

from chipbench.harness import bench, control


@pytest.mark.parametrize("name", ["paper-fit", "paper-serve-batch"])
def test_control_fails_where_the_program_passes(small_cell, name):
    import jax

    cell = small_cell(name)
    run = control.fit_control if name.endswith("fit") else \
        control.serve_control
    for seed in (3, 2 ** 31 + 11):
        got = run(jax, cell, seed)
        ok_prog, _ = bench.checks_line(got["program"], cell.limits)
        ok_ctl, _ = bench.checks_line(got["control"], cell.limits)
        assert ok_prog, got
        assert not ok_ctl, got
