"""``chip_smoke.py`` refuses to run off the chip, and its checks hold on
a tiny namespace on the CPU (kernels interpreted), so the script's own
logic is exercised on every test run, not only on the chip."""
import importlib.util
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu(chip_smoke, capsys):
    assert jax.default_backend() == "cpu"
    assert chip_smoke.main() != 0
    out, err = capsys.readouterr()
    assert "no TPU found" in err
    assert '"ok"' not in out


def test_checks_pass_on_tiny_namespace(chip_smoke):
    lines = []
    assert chip_smoke.run(n_big=2000, n_side=300, n_ops=1200,
                          log=lines.append), "\n".join(lines)
    for fam in chip_smoke.FAMILIES:
        assert any(line.startswith(f"kernel {fam}: launches=")
                   and not line.startswith(f"kernel {fam}: launches=0 ")
                   for line in lines)
    assert not any(line.startswith("check FAIL") for line in lines)
