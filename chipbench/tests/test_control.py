"""The control: the program with its bfloat16 path switched on (the
engines' ``dtype``), one precision below the float32 the configurations
state, must come out not correct. On the chip ``calibrate.py readings``
reads the same at the cells' own sizes."""
import pytest

from chipbench import harness
from chipbench.tests import tiny


@pytest.mark.parametrize("measure,mix", [("knn_class", "ticks"),
                                         ("knn_reg", "reads")])
def test_bfloat16_control_is_not_correct(measure, mix):
    cell = tiny.cell(measure, mix, dtype="bfloat16")
    rec = harness.run(cell, 11, 1.0)
    assert not rec["correct"]
    assert rec["checks"]["dist_err"]["value"] > rec["checks"]["dist_err"][
        "limit"]
