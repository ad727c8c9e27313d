"""The control: the module's plain reference, one precision below the
configuration's, put in the program's place on the timed path.  Through
the harness's own comparison and verdict it reads not correct, while the
program's outputs of the same run read correct (tiny cell, CPU)."""
from bench import harness

SEED = 2 ** 33 + 11


def test_control_in_the_programs_place_reads_not_correct(tiny_root):
    out = harness.run("tiny.lm", SEED, 1.0, False, root=tiny_root,
                      require_tpu=False, control=True)
    assert out["correct"], out["checks"]
    assert out["control_correct"] is False
    ctl = out["control_checks"]["lm_logit_err"]
    assert ctl["value"] > ctl["limit"], ctl
    assert out["checks"]["lm_logit_err"]["value"] < ctl["limit"]
