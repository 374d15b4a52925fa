"""Every generator makes its inputs from the seed alone: the same seed
gives the same data, another seed other data of the same sizes."""

import json
import os

import numpy as np
import pytest

from benchmark.generators import engine_rounds, kv_closed

from .util import REPO, TINY_TRAFFIC

SIZES = {"num_groups": 16, "num_replicas": 3}


def traffic(name):
    with open(os.path.join(REPO, "benchmark", "traffic",
                           name + ".json")) as f:
        return {**json.load(f), **TINY_TRAFFIC[name]}


def kv_fingerprint(seed, name):
    load = kv_closed.make(traffic(name), SIZES, seed)
    stream = kv_closed.PutStream(load)
    return (load["preload"], [stream.next() for _ in range(50)],
            load["readers"], load["writers"])


@pytest.mark.parametrize("name", ["put", "lread"])
def test_kv_closed_is_deterministic_in_the_seed(name):
    assert kv_fingerprint(3_000_000_011, name) == kv_fingerprint(
        3_000_000_011, name)
    a, b = kv_fingerprint(1, name), kv_fingerprint(2, name)
    assert a[1] != b[1]
    assert (a[2], a[3]) == (b[2], b[3])
    assert len(a[0]) == len(b[0])
    if a[0]:
        assert a[0] != b[0]


def test_kv_closed_shapes():
    load = kv_closed.make(traffic("lread"), SIZES, 5)
    assert len(load["preload"]) == 16 * 2
    assert load["readers"] == 4 and load["writers"] == 0
    for g, k, v in load["preload"]:
        assert 0 <= g < 16 and len(k) == 8 and len(v) == 256
        assert 0 not in k, "GroupKV cannot hold a NUL in a key"
    g, k, v = kv_closed.PutStream(load).next()
    assert 0 <= g < 16 and len(k) == 8 and len(v) == 256 and 0 not in k


def test_engine_rounds_is_deterministic_in_the_seed():
    t = traffic("append")
    a = engine_rounds.make(t, SIZES, 2**31 + 5)
    b = engine_rounds.make(t, SIZES, 2**31 + 5)
    c = engine_rounds.make(t, SIZES, 6)
    assert (a["leader_slots"] == b["leader_slots"]).all()
    assert (a["leader_slots"] != c["leader_slots"]).any()
    assert a["leader_slots"].shape == (16,)
    assert set(np.unique(a["leader_slots"])) <= {0, 1, 2}
    assert a["rounds_per_call"] == 4 and a["proposals_per_round"] == 2


def test_p95_is_the_nearest_rank():
    assert kv_closed.p95(list(range(1, 101))) == 96
    assert kv_closed.p95([7.0]) == 7.0


@pytest.mark.parametrize("replicas,shadow_groups", [(3, 8), (5, 6), (5, 5)])
@pytest.mark.parametrize("seed", [7, 2**31 + 13, 2_600_000_011])
def test_the_reference_follows_a_group_of_every_leader_class(
        replicas, shadow_groups, seed):
    """The comparison holds each group equal to its leader slot's class,
    so the sample the reference follows has to hold one of each class."""
    from benchmark.drivers.engine import Driver

    sizes = {"num_groups": 4096, "num_replicas": replicas}
    load = engine_rounds.make(traffic("append"), sizes, seed)
    driver = Driver({"sizes": sizes, "shadow_groups": shadow_groups},
                    traffic("append"), seed, "")
    sample = driver.sample(load)
    assert sample == driver.sample(load) == sorted(set(sample))
    assert len(sample) == shadow_groups
    assert set(load["leader_slots"][sample]) == set(range(replicas))
    other = Driver({"sizes": sizes, "shadow_groups": shadow_groups},
                   traffic("append"), seed + 1, "")
    assert other.sample(load) != sample
