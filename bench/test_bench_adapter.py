"""The trainer still has every attribute the harness reaches into
(``bench/adapter.py``), with the shapes the harness assumes; a rename
or a refactor under ``src/`` fails here, on the CPU, before it can
change what the benchmark times or checks."""
import jax
import pytest

from bench import adapter, reference, run
from bench.models import cgan28
from bench.test_bench_check import SEED, _tiny


@pytest.fixture(scope="module")
def trainer():
    cfg, traffic = _tiny()
    clients = run.clients_of(cgan28, cfg, traffic)
    return cgan28.build_trainer(cfg, traffic, clients, SEED % run.SEED_MOD), cfg


def test_trainer_has_every_attribute(trainer):
    tr, _ = trainer
    assert adapter.missing(tr) == []
    assert sorted(tr.state) == ["D", "G", "opt_d", "opt_g", "step"]
    for net in ("G", "D"):
        assert sorted(tr.state[net]) == ["client", "server"]


def test_adam_state_has_moments(trainer):
    tr, cfg = trainer
    params = cgan28.init_params(cfg, reference.groups_of(cfg),
                                SEED % run.SEED_MOD)
    adapter.set_weights(tr, params)
    now = adapter.trained(tr)
    for key in ("mu", "nu"):
        assert adapter.layout(tr)["G"][0] == \
            jax.tree_util.tree_structure(now[key]["G"])


@pytest.mark.parametrize("name", adapter.PRIVATE)
def test_a_missing_attribute_is_refused(name):
    class Stub:
        pass
    stub = Stub()
    for other in adapter.PRIVATE:
        if other != name:
            setattr(stub, other, None)
    with pytest.raises(RuntimeError, match=name):
        adapter.check(stub)
