"""The metrics' arithmetic and the data drawn from the seed."""

from __future__ import annotations

from types import SimpleNamespace as NS

import numpy as np
import pytest

from chipbench import generate as gen
from chipbench import stats
from chipbench.drivers import library
from chipbench.harness import reader


def test_tree_s_is_all_tree_time_over_the_count():
    rec = {"tree_durations_s": [4.0, 5.0, 6.0]}
    assert reader("tree_s")(rec) == pytest.approx(5.0)
    assert reader("tree_s")({"tree_durations_s": []}) is None
    with pytest.raises(ValueError):
        stats.mean_time([])


def test_trace_readers():
    rec = {"tree_durations_s": [4.0, 5.0], "setup_s": 12.5,
           "trace": NS(busy_s=7.5, window_s=10.0)}
    assert reader("engine_device_s.lib")(rec) == pytest.approx(3.75)
    assert reader("device_idle_share.lib")(rec) == pytest.approx(0.25)
    assert reader("setup_s")(rec) == 12.5
    # an untraced run, or one with no tree, reads nothing
    assert reader("engine_device_s.lib")({"tree_durations_s": [4.0]}) is None
    assert reader("engine_device_s.lib")({**rec, "tree_durations_s": []}) is None
    assert reader("device_idle_share.lib")({}) is None


def test_mixture_points_are_a_function_of_the_seed():
    x = gen.gaussian_mixture(gen.rng_for(2**31 + 1, 4), 100, 128, 12)
    y = gen.gaussian_mixture(gen.rng_for(2**31 + 1, 4), 100, 128, 12)
    z = gen.gaussian_mixture(gen.rng_for(2**31 + 2, 4), 100, 128, 12)
    assert x.dtype == np.float32 and x.shape == (100, 128)
    assert np.array_equal(x, y) and not np.array_equal(x, z)
    with pytest.raises(ValueError):
        gen.gaussian_mixture(gen.rng_for(1), 4, 8, 5)


def test_every_seed_draws_the_same_work():
    """The pool and the sampled trees have the same sizes for every seed,
    however large; only the points and the sample differ."""
    cfg = {"n_points": 64, "dim": 8, "components": 4}
    trf = {"pool": 3, "check_trees": 1}
    runs = [NS(seed=s, config=cfg, traffic=trf)
            for s in (0, 2**31 + 7, 2**40 + 3)]
    sets = [library.point_sets(r) for r in runs]
    for warm, pool in sets:
        assert warm.shape == (64, 8) and [p.shape for p in pool] == [(64, 8)] * 3
    assert not np.array_equal(sets[0][1][0], sets[1][1][0])
    for r in runs:
        picks = library.sampled(r, 10)
        assert len(picks) == 1 and 0 <= picks[0] < 10
        assert picks == library.sampled(r, 10)
