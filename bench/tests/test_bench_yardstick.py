"""The yardstick kept with the benchmark: generator, percentiles, peaks,
the dict reference, and the traffic mixes (CPU only, no store)."""

import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import gen  # noqa: E402
import peaks  # noqa: E402
import reference  # noqa: E402
import ycsb  # noqa: E402

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic"))
               if f.endswith(".json"))
SEEDS = (0, 7, 2**31 + 11, 2**40 + 3)


def load_mix(name):
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


def chunks(mix, seed, n=3, stream=0, records=5000):
    s = gen.OpStream(mix, records=records, value_size=128, seed=seed,
                     stream=stream)
    return [s.next_chunk() for _ in range(n)]


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("seed", SEEDS)
def test_mix_is_deterministic_in_its_seed(mix, seed):
    m = load_mix(mix)
    assert chunks(m, seed) == chunks(m, seed)


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_work(mix):
    """Seeds differ in order and ids, never in how many of each call."""
    m = load_mix(mix)
    a, b = chunks(m, 1), chunks(m, 2**31 + 5)
    assert a != b
    for (ka, keys_a, _), (kb, keys_b, _) in zip(a, b):
        assert sorted(ka) == sorted(kb)
        assert [len(k) for k in keys_a] == [len(k) for k in keys_b]


@pytest.mark.parametrize("mix", MIXES)
def test_warmup_stream_differs_from_the_window(mix):
    m = load_mix(mix)
    w = gen.OpStream(m, records=5000, value_size=128, seed=1, stream=1,
                     warmup=True)
    assert w.next_chunk() != chunks(m, 1, n=1)[0]


def test_inserts_never_collide_with_preload_or_warmup():
    m = {"mix": {"insert": 1.0}}
    ids = set()
    for seed, warm in ((0, True), (0, False), (2**31 + 1, False)):
        s = gen.OpStream(m, records=1000, value_size=128, seed=seed,
                         stream=1 if warm else 0, warmup=warm)
        _, keys, values = s.next_chunk()
        new = {ycsb.id_of(k) for k in keys}
        assert min(new) >= 1000 and not (new & ids)
        ids |= new
        assert all(v == ycsb.value_of(ycsb.id_of(k), 128)
                   for k, v in zip(keys, values))


def test_preload_does_not_depend_on_a_seed():
    a = list(gen.preload_records(300, 128))
    b = list(gen.preload_records(300, 128))
    assert a == b
    assert a[5] == (ycsb.key_of(5), ycsb.value_of(5, 128))
    ref = reference.Reference(300, 128)
    assert all(ref.get(k) == v for k, v in a)


@pytest.mark.parametrize("i", [0, 1, 999_999, 2**31 + 7, 2**47 - 1])
def test_key_of_inverts(i):
    k = ycsb.key_of(i)
    assert len(k) == 16 and ycsb.id_of(k) == i


def test_id_of_refuses_foreign_keys():
    assert ycsb.id_of(b"nouser") is None
    assert ycsb.id_of(b"userzzzzzzzzzzzz") is None


def test_reference_follows_writes_and_preload():
    ref = reference.Reference(10, 32)
    k = ycsb.key_of(3)
    assert ref.get(k) == ycsb.value_of(3, 32)
    ref.put(k, b"new")
    assert ref.get(k) == b"new"
    assert ref.get(ycsb.key_of(10)) is None
    assert reference.count_mismatches([b"new", None], [k, b"x"], ref) == 0


@pytest.mark.parametrize("n", [1, 2, 10, 1001])
def test_percentiles_match_numpy(n):
    lat = np.random.default_rng(n).exponential(100.0, n).tolist()
    got = ycsb.percentiles(lat)
    want = np.percentile(lat, [50.0, 99.0, 99.9])
    assert np.allclose([got[50.0], got[99.0], got[99.9]], want)


def test_zipfian_is_skewed_and_in_range():
    z = ycsb.ZipfianGenerator(10_000, seed=3).sample(50_000)
    assert z.min() >= 0 and z.max() < 10_000
    assert (z == 0).mean() > 0.05      # the head is hot
    assert len(np.unique(z)) > 1000    # and the tail is drawn


def test_peaks_table_is_keyed_by_device_kind():
    assert peaks.peaks("TPU v5 lite").hbm_bw == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
