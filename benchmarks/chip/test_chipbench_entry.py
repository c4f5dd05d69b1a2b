"""The entry point refuses to run without a TPU: it names the backend it
found, prints no result line, and exits non-zero."""
import jax

import run_cell


def test_refuses_without_tpu(capsys):
    assert jax.default_backend() == "cpu"
    rc = run_cell.main(["--workload", "spotify-1m.steady", "--seed",
                        str(2 ** 31 + 5), "--seconds", "10", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert "no TPU found" in err
    assert "platform=cpu" in out
    assert '"correct"' not in out
