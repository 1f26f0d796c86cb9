"""The correctness comparison separates the program from its control: at
a size a test run holds, the program's served tokens pass the limit and
the fp8 W8A8 reference put in its place fails it, on three seeds."""
import pytest

from bench import control, harness, tiny


@pytest.fixture(scope="module")
def api():
    from repro.models import build_model
    return build_model(
        harness.architecture(tiny.CONFIG).program_config(tiny.CONFIG))


@pytest.mark.parametrize("seed", [2, 5, 2**31 + 11])
def test_program_passes_and_control_fails(api, seed):
    ref = harness.load_module("references", "dense_gqa")
    program, ctl, n = control.readings(tiny.CONFIG, tiny.MIX, ref, seed, api,
                                       2 * tiny.MIX["concurrency"], ("fp8",))
    assert n >= 80
    assert program <= tiny.LIMIT < ctl["fp8"]
