"""chip_smoke.py on the CPU: it refuses to run without a TPU, and its
phases run end to end at smoke size (interpret-mode kernels; the
lowered-step kernel check only holds on a TPU, so it is stubbed here)."""
import importlib.util
import json
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def no_kernel_check(smoke, monkeypatch):
    monkeypatch.setattr(smoke, "assert_kernels_compiled",
                        lambda name, fn, *args: None)
    return smoke


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_exits_nonzero_without_tpu(smoke, monkeypatch, capsys, argv):
    monkeypatch.setattr("sys.argv", ["chip_smoke.py", *argv])
    with pytest.raises(SystemExit) as e:
        smoke.main()
    assert e.value.code not in (0, None)
    assert "no TPU" in str(e.value.code)
    for line in capsys.readouterr().out.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_serve_phase_smoke(no_kernel_check):
    no_kernel_check.serve_phase(full=False, slots=3, requests=4,
                                prompt_len=8, gen=6, topk=4)


def test_train_phase_smoke(no_kernel_check):
    no_kernel_check.train_phase(full=False, steps=2, batch=2, seq=16)


def test_retrieval_phase_smoke(no_kernel_check):
    no_kernel_check.retrieval_phase(preset="smoke", slots=4, requests=6,
                                    impl="xla")


def test_failed_check_stops_with_message(smoke):
    with pytest.raises(SystemExit, match="chip_smoke: FAILED: boom"):
        smoke.check(False, "boom")
