"""The controls of ``correct``: the reference at each lower precision
(int8 matmuls; fp8 attention) put in the program's place has to come out
not correct where the program comes out correct.
At the cells' own sizes this is measured on the chip by ``bench/control.py``
(readings in PERF.md); here it runs on the CPU at a width of 256, where
the limits below sit between the two sets of readings."""
import pytest

from conftest import TINY_CFG, make_root

WIDE = dict(TINY_CFG, hidden_size=256, intermediate_size=512, head_dim=64)
# readings on seeds 1-3, logit_err: prefill, program <= 0.0191, int8 >= 0.0360,
# fp8 attention >= 0.0875; decode (all 4 sessions), program <= 0.0112, int8
# >= 0.0269, fp8 attention >= 0.106; the token gaps do not separate at this
# size (0 to 0.013 on both sides)
LIMITS = {"tiny.prefill": {"logit_err": 0.027, "token_gap": 0.02},
          "tiny.decode": {"logit_err": 0.015, "token_gap": 0.02}}
DECODE_STEPS = 24


@pytest.mark.parametrize("cell", ["tiny.prefill", "tiny.decode"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_where_the_program_passes(cpu_run, tmp_path, cell, seed):
    c = cpu_run.load_cell(cell, make_root(tmp_path / "checkout", WIDE))
    st = cpu_run.setup(c, seed)
    win = cpu_run.window(st, 0)           # prefill: one cycle; decode: one step
    if c.mix["kind"] == "decode":
        for _ in range(DECODE_STEPS):
            cpu_run.window(st, 0)
    cpu_run.release_program(st)
    program = cpu_run.compare(st, win)
    assert cpu_run.judge(program, LIMITS[cell])[0], program
    for name in st.ref.CONTROLS:
        control = cpu_run.compare(st, win, control=name)
        assert not cpu_run.judge(control, LIMITS[cell])[0], (name, control)
