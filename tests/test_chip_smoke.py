"""chip_smoke.py's phases at a tiny size on the CPU backend (Pallas in
interpret mode): the same checks the chip run makes, so a broken phase
shows up here before it costs a chip call. The platform check itself
must refuse the CPU backend."""
import importlib.util
import os

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(_ROOT, "chip_smoke.py"))
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)


def test_platform_check_refuses_cpu():
    with pytest.raises(SystemExit, match="not 'tpu'"):
        cs.check_platform()


def test_main_on_cpu_prints_no_result(capsys):
    with pytest.raises(SystemExit) as exc:
        cs.main([])
    assert exc.value.code != 0
    assert '"ok"' not in capsys.readouterr().out


def test_phase_train_tiny():
    out = cs.phase_train(num_clients=4, batch=2, steps=1, base_size=8)
    assert out["k"] == 2
    assert out["weight_err"] <= 1e-4 and out["param_err"] <= 5e-4


def test_phase_serve_tiny():
    errs = cs.phase_serve(n_requests=5)
    assert set(errs) == set(cs.SERVE_MIXES)


def test_phase_lm_tiny():
    assert cs.phase_lm(batch=2, seq=24, prompt=16, gen=4)["err"] <= 2e-4


def _check_four_chip():
    """8 clients round-robin on 7 profiles: the dense buffer (8 rows)
    shards over 4 devices, the per-group chunk stream falls back; 28
    clients (4 per profile): both shard."""
    out = cs.phase_four_chip(n_dev=4, populations=(8, 28), batch=2,
                             chunk=2, base_size=8)
    assert out[8]["paths"]["dense round @4"].startswith("sharded")
    assert out[8]["paths"]["chunked round @4"] == "one device (FELL BACK)"
    assert out[28]["paths"]["chunked round @4"].startswith("sharded")
    assert out[28]["paths"]["train step"].startswith(
        "dataset rows sharded for 7/7 groups")


def test_phase_four_chip_tiny_multihost(multihost):
    multihost("test_chip_smoke", "_check_four_chip")
