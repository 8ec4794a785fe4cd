"""The control: the plain reference one precision step lower (fp8 ->
int4, bf16 -> fp8, f32 -> bf16) put in the program's place.  At the
rehearsal sizes the program's readings stay under the rehearsal limits
and the control's exceed one of them, in both kinds of cell; on the chip
``bench/calibrate.py`` takes the same readings at the cells' own sizes."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import calibrate, spec  # noqa: E402

# loss_gap is read but not compared, as in the chip cell's limits
TRAIN_LIMITS = {"grad_gap": 0.02, "change_gap": 0.006}
SERVE_LIMITS = {"logit_gap": 0.4}


def _check(table, limits):
    for row in table["program"]:
        assert all(row[k] <= v for k, v in limits.items()), row
    for row in table["control"]:
        assert any(row[k] > v for k, v in limits.items()), row


def test_train_control_fails():
    table = calibrate.calibrate_train(spec.cell("dsmoe-train"), [3], [11],
                                      True, "pallas_interpret")
    _check(table, TRAIN_LIMITS)
    for row in table["half_batch"]:
        assert any(row[k] > v for k, v in TRAIN_LIMITS.items()), row


def test_serve_control_fails():
    table = calibrate.calibrate_serve(spec.cell("dsmoe-decode"), [3], [11],
                                      True, "pallas_interpret")
    _check(table, SERVE_LIMITS)
