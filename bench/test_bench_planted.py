"""The checked round's planted activations are clustered by the
program's stage 3 exactly as the reference clusters them, on the seeds
where one k-means++ seeding used to put two centres into one blob
(615040690, 615040693) and on blob counts 2, 3 and 4."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, run, spec
from bench.models import cgan28
from repro.core.clustering import cluster_activations_jax

NUM_CLIENTS = 100                   # cgan28-paper100
FEATURES = cgan28.ema_features(spec.read_json(
    spec.config_path("cgan28-paper100")))
SEEDS = [615040690, 615040693, 2700000001, 2800000004, 3000000001,
         2147483801]


@pytest.fixture(scope="module")
def stage3():
    return jax.jit(lambda a, k: cluster_activations_jax(a, k))


@pytest.mark.parametrize("seed", SEEDS)
def test_program_stage3_finds_planted_partition(stage3, seed):
    seed31 = seed % run.SEED_MOD
    acts = reference.planted_activations(NUM_CLIENTS, FEATURES, seed31)
    ref_labels, ref_k = reference.cluster(acts)
    assert ref_k == 2 + seed31 % 3
    # the trainer's key for its first clustered round
    key = jax.random.split(jax.random.PRNGKey(seed31 + 2))[1]
    labels, k, _ = stage3(jnp.asarray(acts), key)
    assert int(k) == ref_k
    np.testing.assert_array_equal(np.asarray(labels), ref_labels)
