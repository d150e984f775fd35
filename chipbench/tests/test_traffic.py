"""The benchmark's traffic generator."""
import numpy as np
import pytest

from chipbench import harness
from chipbench.traffic import generate

CONFIG = harness.config("kits19-cohort")
MIX = generate.load("table2-tumours")
# the studies of the first three patients: kidneys and tumours
SOME = {"cases": [cid for cid, _ in CONFIG["images"][:6]]}


def _shape_bucket(roi_dims, step=32):
    """The program's bucket of an ROI: each padded axis up to 32."""
    return tuple(max(step, -(-(d + 2) // step) * step) for d in roi_dims)


@pytest.fixture(scope="module")
def seed0():
    return generate.build_cases(CONFIG, SOME, 0)


def test_cases_are_deterministic_per_seed(seed0):
    again = generate.build_cases(CONFIG, SOME, 0)
    other = generate.build_cases(CONFIG, SOME, 1)
    for a, b, c in zip(seed0, again, other):
        assert np.array_equal(a.mask, b.mask)
        assert np.array_equal(a.image, b.image)
        assert not np.array_equal(a.image, c.image)
    assert any(not np.array_equal(a.mask, c.mask)
               for a, c in zip(seed0, other))


@pytest.fixture(scope="module")
def boxes0():
    return [(c.box, _shape_bucket(c.roi_dims))
            for c in generate.build_cases(CONFIG, MIX, 0)]


@pytest.mark.parametrize("seed", list(range(1, 12)) + [2**31 + 11])
def test_every_case_keeps_its_box_and_bucket(seed, boxes0):
    cases = generate.build_cases(CONFIG, MIX, seed)
    assert [c.name for c in cases] == MIX["cases"]
    for c in cases:
        assert c.mask.shape == c.image.shape
        assert c.mask.dtype == bool and c.image.dtype == np.float32
        idx = np.nonzero(c.mask)
        assert tuple(int(i.min()) for i in idx) == c.box[0]
        assert tuple(int(i.max()) + 1 for i in idx) == c.box[1]
    assert [(c.box, _shape_bucket(c.roi_dims)) for c in cases] == boxes0


def test_rois_fill_their_images_as_in_table_2(seed0):
    for c in seed0:
        fill = np.prod(c.roi_dims) / np.prod(c.mask.shape)
        assert fill > 0.3, (c.name, c.roi_dims, c.mask.shape)


def test_dataset_order_cycles_the_configuration():
    it = generate.order({"order": "dataset"}, 5, seed=3)
    assert [next(it) for _ in range(12)] == [0, 1, 2, 3, 4] * 2 + [0, 1]


def test_shuffled_order_is_a_seeded_permutation_per_pass():
    def first(seed, k):
        it = generate.order({"order": "shuffled"}, 20, seed)
        return [next(it) for _ in range(k)]

    a = first(7, 40)
    assert sorted(a[:20]) == list(range(20))
    assert sorted(a[20:]) == list(range(20))
    assert a[:20] != a[20:]
    assert a == first(7, 40)
    assert a[:20] != first(8, 20)
    assert first(2**33 + 1, 20) == first(2**33 + 1, 20)
    with pytest.raises(ValueError):
        generate.order({"order": "bursty"}, 20, 1)
