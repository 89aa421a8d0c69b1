"""A run refuses any device but a TPU: exit non-zero, no result line."""

import pytest

from chipbench import run as R


def test_run_refuses_cpu_device(capsys):
    import jax
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as exc:
        R.main(["--workload", "flux.sparse.s28", "--seed", str(2 ** 33 + 1),
                "--seconds", "1", "--trace", "0"])
    assert exc.value.code not in (0, None)
    assert "no TPU" in str(exc.value.code)
    assert capsys.readouterr().out == ""


def test_unknown_workload_is_refused(capsys):
    with pytest.raises(KeyError):
        R.main(["--workload", "no.such.cell", "--seed", "1", "--seconds", "1"])
    assert capsys.readouterr().out == ""
