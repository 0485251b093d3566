"""The generators: the same seed gives the same client data; another seed
gives the same set of OPs in another order."""
import numpy as np

from chipbench.harness import common, train

BENCH = common.load_benchmark()


def test_training_inputs_repeat_for_a_seed():
    for name in ("vgg5.fedadapt-k64", "qwen3-0.6b-cut4.fed-k4"):
        cell = common.Cell(BENCH, name)
        first, last = list(cell.mix["ops"])[0], list(cell.mix["ops"])[-1]
        cell.mix = dict(cell.mix, clients=4, samples_per_client=8,
                        eval_samples=4, ops={first: 2, last: 2})
        a, b = train.prepare(cell, 2 ** 31 + 5), train.prepare(cell, 2 ** 31 + 5)
        c = train.prepare(cell, 2 ** 31 + 6)
        assert a["ops"] == b["ops"] and a["fl_seed"] == b["fl_seed"]
        assert sorted(a["ops"]) == sorted(c["ops"])
        for x, y in zip(a["clients"], b["clients"]):
            for k in x:
                np.testing.assert_array_equal(x[k], y[k])
        key = "images" if "images" in a["test"] else "tokens"
        assert not np.array_equal(a["test"][key], c["test"][key])
        assert 0 <= a["fl_seed"] < 2 ** 31


def test_derived_seeds_take_seeds_past_32_bits():
    s = common.derive_seed(2 ** 31 + 12345, "data")
    assert 0 <= s < 2 ** 31
    assert s == common.derive_seed(2 ** 31 + 12345, "data")
    assert s != common.derive_seed(2 ** 31 + 12345, "ops")
    assert s != common.derive_seed(12345, "data")
