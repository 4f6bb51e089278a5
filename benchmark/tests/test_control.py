"""The control of "How ``correct`` is decided", at a size a test run can
hold: the reference computed in fp8 in the program's place reads far
wider gaps than the program does, on three seeds.  (On the chip, at the
cells' own sizes, the same script set the limits: PERF.md.)"""

from benchmark import control
from conftest import TINY_ROOT

SEEDS = "21,22,2147483659"


def read(workload, tmp_path):
    return control.main(
        ["--workload", workload, "--seeds", SEEDS, "--control-seeds",
         SEEDS, "--seconds", "2", "--out", str(tmp_path)],
        root=TINY_ROOT, require_tpu=False)


def test_fp8_in_the_engines_place_is_not_correct(tmp_path):
    got = read("gpt2-tiny.serve-tiny-open", tmp_path)
    for key in ("logit_gap_widest", "logit_gap_mean"):
        assert got[key]["control_smallest"] > 3 * got[key]["sound_largest"]


def test_fp8_in_the_steps_place_is_not_correct(tmp_path):
    got = read("gpt2-tiny.train-tiny", tmp_path)
    key = "token_loss_rms_gap"
    assert got[key]["control_smallest"] > 1.5 * got[key]["sound_largest"]
