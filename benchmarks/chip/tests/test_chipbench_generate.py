"""The generator: the K schedule is fixed by the mix, whatever the seed;
rows within a round never repeat."""
import chipbench_tiny  # noqa: F401  (paths)
import numpy as np

import generate
import harness

TRAIN = harness.load_json("traffic", "fedagrac-kasync")


def test_k_schedule_is_the_mix_s_and_not_the_seed_s():
    ks = generate.k_schedule(TRAIN)
    assert ks.shape == (TRAIN["k_rounds"], TRAIN["clients"])
    assert ks.dtype == np.int32 and ks.min() >= TRAIN["k_min"]
    # the cell's k_max, whatever --seed the run gets
    assert int(ks.max()) == 7
    assert ks.tolist() == [[3, 4], [7, 5], [1, 4], [3, 4]]
    for seed in (0, 1, 2**31 + 5, 3_000_000_000_123):
        rows = generate.round_rows(seed, TRAIN, int(ks.max()), 8)
        assert rows.shape == (8, 2, 7, 4)
    assert np.array_equal(generate.k_schedule(dict(TRAIN)), ks)


def test_round_rows_differ_within_a_round_and_follow_the_seed():
    a = generate.round_rows(2**33 + 1, TRAIN, 7, 16)
    b = generate.round_rows(2**33 + 1, TRAIN, 7, 16)
    c = generate.round_rows(2**33 + 2, TRAIN, 7, 16)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    for t in range(16):
        for i in range(2):
            assert len(np.unique(a[t, i])) == a[t, i].size
    assert a.max() < TRAIN["seqs_per_client"]

