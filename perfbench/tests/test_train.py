"""The benchmark's trainer and the utility thresholds of its banks."""
import numpy as np

import jax

from lib import frames, train
from reference import cnn

TINY = {"name": "tiny", "input_shape": [8, 8, 3], "convs": [[4, 5, True]],
        "fcs": [8], "n_classes": 3}


def test_exit_threshold_is_the_smallest_that_meets_the_accuracy():
    # margins 0..0.99; frames below 0.5 are wrong, above right
    margin = np.linspace(0.0, 0.99, 100)
    correct = margin >= 0.5
    t = cnn.exit_threshold(margin, correct, 0.9)
    exited = margin > t
    assert correct[exited].mean() >= 0.9
    # one grid step lower would let too many wrong frames out
    lower = np.quantile(margin, np.linspace(0.0, 0.98, 50))
    below = lower[lower < t].max()
    assert correct[margin > below].mean() < 0.9


def test_exit_threshold_falls_back_to_the_grid_top():
    margin = np.linspace(0.0, 1.0, 64)
    t = cnn.exit_threshold(margin, np.zeros(64, bool), 0.9)
    assert t == np.quantile(margin, 0.98)


def test_training_lowers_the_loss_and_is_deterministic():
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    protos = frames.prototypes(k[0], TINY["n_classes"], TINY["input_shape"])
    envs = frames.prototypes(k[1], 2, TINY["input_shape"])
    settings = {"batch": 32, "lr": 0.01}

    def run():
        return jax.jit(lambda a, b: train.train(
            TINY, cnn.init_params(TINY, a), protos, envs, b, settings, 1.0,
            40))(k[2], k[3])

    params, losses = run()
    losses = np.asarray(losses)
    assert losses[-5:].mean() < 0.5 * losses[:5].mean()
    again, _ = run()
    for p, q in zip(params, again):
        np.testing.assert_array_equal(np.asarray(p["w"]), np.asarray(q["w"]))
