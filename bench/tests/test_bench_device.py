"""The harness refuses to measure anything but a TPU it has peaks for."""
import dataclasses

import pytest

import tinycell
from harness import device


@dataclasses.dataclass
class FakeDevice:
    platform: str
    device_kind: str


PEAKS = device.load_peaks()


def test_v5e_row_has_its_source():
    row = PEAKS["TPU v5 lite"]
    assert row["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in row["source"]


def test_accepts_a_known_tpu():
    row = device.check_devices([FakeDevice("tpu", "TPU v5 lite")], 1, PEAKS)
    assert row is PEAKS["TPU v5 lite"]


@pytest.mark.parametrize("devices, chips", [
    ([FakeDevice("cpu", "cpu")], 1),
    ([FakeDevice("tpu", "TPU v9 imaginary")], 1),
    ([FakeDevice("tpu", "TPU v5 lite")], 4),
    ([], 1),
])
def test_refuses(devices, chips):
    with pytest.raises(device.DeviceError):
        device.check_devices(devices, chips, PEAKS)


def test_run_refuses_the_cpu(capsys):
    import jax

    if jax.devices()[0].platform != "cpu":
        pytest.skip("this check needs a machine without a TPU")
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_run", tinycell.BENCH / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    assert run.main(["--workload", "bitmap16m.heavy.closed32", "--seed",
                     "1", "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""
