"""What decides ``correct``: a sound run passes, the control and each
fault the cell can have fail, at a size the Pallas interpreter runs."""

import jax
import jax.numpy as jnp
import pytest

from chipbench import bench as B
from chipbench import reference
from chipbench import run as R
from chipbench_cells import INTERPRET, TickClock

SEED = 2 ** 33 + 17


@pytest.fixture(autouse=True)
def _clock(monkeypatch):
    monkeypatch.setattr(R, "time", TickClock())


def _run(cell, **overrides):
    bench = B.load_benchmark()
    return R.run_cell(bench, cell, SEED, 3.5, False, jax.devices(),
                      R.time.perf_counter(), engine_overrides=overrides)


def test_sound_run_is_correct(tiny_cell):
    res = _run(tiny_cell, **INTERPRET)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    for c in res["checks"].values():
        assert c["value"] < c["limit"] / 3


def test_control_fails(tiny_cell):
    """The reference with float8 matrix operands is not correct."""
    server = R.Server(tiny_cell, SEED, INTERPRET)
    x0, ref = server.reference(1)
    _, low = server.reference(1, compute=jnp.float8_e4m3fn)
    got = reference.compare(low, ref, x0)
    limits = tiny_cell["model"]["correct"]
    assert any(got[k] > limits[k] for k in limits), got


def _state_unchanged(x0, out):
    return x0


def _answer_altered(x0, out):
    return out.at[:, 7].add(1.0)


@pytest.mark.parametrize("fault", [_state_unchanged, _answer_altered])
def test_fault_makes_run_incorrect(tiny_cell, monkeypatch, fault):
    from repro.launch import batching

    real = batching.sample

    def broken(*args, x0, **kw):
        return fault(x0, real(*args, x0=x0, **kw))

    monkeypatch.setattr(batching, "sample", broken)
    res = _run(tiny_cell, backend="xla", interpret=True)
    assert res["attempted"] == 1
    assert res["checks"]["rel_l2"]["value"] > res["checks"]["rel_l2"]["limit"]
    assert not res["correct"]
