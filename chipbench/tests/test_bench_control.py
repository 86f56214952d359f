"""The control at toy sizes on the CPU: the reference in 8-bit floats, put
in the program's place, must read a widest logit gap above the limit that
the served bf16 program stays under."""
import _tiny
import control

LIMIT = 0.02    # the toy limit of test_bench_faults.py


def test_control_fails_where_the_program_passes(tmp_path):
    wl = _tiny.workload("agent", max_logit_gap=LIMIT)
    r = control.readings(wl, 2 ** 31 + 9, 2.0, True, require_tpu=False,
                         out_dir=tmp_path)
    assert r["served_tokens"] >= 120
    assert r["program_gap"] <= LIMIT < r["control_gap"], r
