"""The benchmark's copy of the population generator equals the
program's ``build_scenario`` at the same settings."""
import numpy as np
import pytest

from bench import population
from bench.models import cgan28
from repro.data import build_scenario

SHAPE = (cgan28.IMAGE, cgan28.NUM_CLASSES)


@pytest.mark.parametrize("num_clients,base_size,seed", [(8, 30, 0), (6, 45, 3)])
def test_copy_equals_build_scenario(num_clients, base_size, seed):
    ours = population.SCENARIOS["2dom_noniid"](num_clients, base_size, seed,
                                               *SHAPE)
    theirs = build_scenario("2dom_noniid", num_clients=num_clients,
                            base_size=base_size, seed=seed)
    assert len(ours) == len(theirs)
    for (dom, imgs, labs), spec in zip(ours, theirs):
        assert dom == spec.domain
        np.testing.assert_array_equal(imgs, spec.images)
        np.testing.assert_array_equal(labs, spec.labels)


def test_cache_round_trip(tmp_path, monkeypatch):
    monkeypatch.setattr(population, "CACHE_DIR", str(tmp_path))
    traffic = {"scenario": "2dom_noniid", "base_size": 30, "partition_seed": 0}
    built = population.population(traffic, 4, *SHAPE)
    cached = population.population(traffic, 4, *SHAPE)
    assert len(list(tmp_path.iterdir())) == 1
    for a, b in zip(built, cached):
        assert a[0] == b[0]
        np.testing.assert_array_equal(a[1], b[1])
        np.testing.assert_array_equal(a[2], b[2])
