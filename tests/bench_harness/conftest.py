"""The rehearsals arm the program's compile counters as every benchmark
run does (``program.arm_compile_counters``); a run is a process of its own,
a test is not, so what a rehearsal armed is disarmed again."""

import pytest


@pytest.fixture(autouse=True)
def _leave_the_program_disarmed():
    yield
    from photon_ml_tpu.obs import compile as obs_compile

    obs_compile.disarm()
