"""Seeded generators: a seed gives the same inputs, two seeds differ, and
every seed gets the same amount of work."""

import pathlib
from collections import Counter

import jax.numpy as jnp
import numpy as np
import pytest

from bench import data, harness

SERVICE = harness.load_file(pathlib.Path(data.__file__).parent / "loops" /
                            "open_service.py")

MIX = {"rate_per_s": 50.0, "tenants": 3, "zipf_theta": 0.99,
       "update_share": 0.05, "panel_pool": 8, "factor_pool": 8,
       "check_share": 0.25, "solve_cols": 4, "update_rank": 2}
BIG_SEED = 2 ** 33 + 12345


def test_schedule_repeats_for_a_seed_and_differs_between_seeds():
    a = data.open_loop_schedule(MIX, 20.0, BIG_SEED)
    b = data.open_loop_schedule(MIX, 20.0, BIG_SEED)
    c = data.open_loop_schedule(MIX, 20.0, BIG_SEED + 1)
    assert a == b
    assert a != c
    assert [r.due for r in a] != [r.due for r in c]
    assert [(r.tenant, r.item) for r in a] != [(r.tenant, r.item) for r in c]


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_every_seed_gets_the_same_work(seed):
    base = data.open_loop_schedule(MIX, 20.0, 1)
    mine = data.open_loop_schedule(MIX, 20.0, seed)
    assert len(mine) == len(base) == 1000
    assert Counter((r.op, r.tenant) for r in mine) == Counter(
        (r.op, r.tenant) for r in base)
    assert sum(r.check for r in mine) == sum(r.check for r in base)
    def gaps(reqs):
        return sorted(np.diff([0.0] + [r.due for r in reqs]))

    np.testing.assert_allclose(gaps(mine), gaps(base), rtol=1e-9)
    assert all(r.op == "solve" for r in mine if r.check)
    assert [r.due for r in mine] == sorted(r.due for r in mine)


def test_schedule_follows_the_mix():
    reqs = data.open_loop_schedule(MIX, 20.0, 3)
    ops = Counter(r.op for r in reqs)
    assert ops["update"] == 50 and ops["solve"] == 950
    shares = np.array([sum(r.tenant == t for r in reqs) for t in range(3)])
    np.testing.assert_allclose(shares / len(reqs),
                               data.zipf_shares(3, 0.99), atol=2e-3)
    assert reqs[-1].due == pytest.approx(20.0, rel=0.05)


def test_bursts_keep_the_work_and_fall_silent_between_phases():
    bursty = dict(MIX, on_s=2.0, off_s=3.0)
    steady = data.open_loop_schedule(MIX, 20.0, 4)
    reqs = data.open_loop_schedule(bursty, 20.0, 4)
    assert Counter((r.op, r.tenant, r.check) for r in reqs) == Counter(
        (r.op, r.tenant, r.check) for r in steady)
    phase = np.array([r.due for r in reqs]) % 5.0
    assert (phase < 2.0).all()
    assert reqs[-1].due == pytest.approx(17.0, abs=0.5)   # 4th phase ends
    assert data.open_loop_schedule(dict(MIX, on_s=20.0, off_s=0.0), 20.0,
                                   4) == steady


def test_versions_count_each_tenants_earlier_updates():
    reqs = data.open_loop_schedule(MIX, 4.0, 9)
    versions, history = SERVICE.plan_versions(reqs, 3)
    seen = {t: len(history[t]) - sum(r.op == "update" and r.tenant == t
                                     for r in reqs) for t in range(3)}
    assert seen == {0: 1, 1: 1, 2: 1}          # the warm-up's updates
    for r, v in zip(reqs, versions):
        assert v == seen[r.tenant]
        if r.op == "update":
            seen[r.tenant] += 1


def test_device_inputs_repeat_for_a_seed_and_differ_between_seeds():
    a = data.spd_matrix(64, BIG_SEED, 0)
    assert jnp.array_equal(a, data.spd_matrix(64, BIG_SEED, 0))
    assert not jnp.array_equal(a, data.spd_matrix(64, BIG_SEED + 1, 0))
    assert not jnp.array_equal(a, data.spd_matrix(64, BIG_SEED, 1))
    assert jnp.allclose(a, a.T)
    assert float(jnp.linalg.eigvalsh(a).min()) > 0.9
    p1, f1 = SERVICE.pools(64, MIX, BIG_SEED)
    p2, f2 = SERVICE.pools(64, MIX, BIG_SEED)
    p3, f3 = SERVICE.pools(64, MIX, 5)
    assert len(p1) == 8 and p1[0].shape == (64, 4) and f1[0].shape == (64, 2)
    assert all(jnp.array_equal(x, y) for x, y in zip(p1 + f1, p2 + f2))
    assert not any(jnp.array_equal(x, y) for x, y in zip(p1 + f1, p3 + f3))


def test_negative_seed_is_refused():
    with pytest.raises(ValueError):
        data.seed_words(-1)


@pytest.mark.parametrize("size", [1, 2, 5])
def test_checked_answers_are_drawn_from_the_whole_window(size):
    """The closed loop's sample is uniform over every call, repeats for a
    seed, and differs between seeds."""
    inverse = harness.load_file(pathlib.Path(data.__file__).parent /
                                "loops" / "closed_inverse.py")

    def sample(seed, calls=60):
        r = inverse.Reservoir(size, seed)
        for i in range(calls):
            r.offer(i, f"x{i}")
        return [i for i, _ in r.items()]

    assert sample(BIG_SEED) == sample(BIG_SEED)
    assert len(sample(BIG_SEED)) == size
    assert sample(3, calls=size - 1) == list(range(size - 1))
    seen = Counter(i for seed in range(4000) for i in sample(seed))
    assert max(seen) >= 50 and min(seen) < 10
    counts = np.array([seen[i] for i in range(60)])
    assert counts.min() > 0.5 * counts.mean()
