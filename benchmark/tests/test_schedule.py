import pytest

from harness.schedule import arrivals, slot_spread


def test_slot_spread_is_even_and_inside_the_spread():
    out = slot_spread(4, 1, 12.0, 4.0, 2.0)
    assert out == [16.0, 16.5, 17.0, 17.5]


def test_arrivals_do_not_depend_on_anything_but_the_mix():
    shape = {"shape": "slot_spread", "offset_s": 4.0, "spread_s": 2.0}
    a = arrivals(shape, 32768, 2, 12.0)
    assert a == arrivals(shape, 32768, 2, 12.0)
    assert len(a) == 2 and all(len(s) == 32768 for s in a)
    assert a[0][0] == 4.0 and a[1][0] == 16.0
    assert max(a[1]) < 18.0


def test_unknown_arrival_shape_is_refused():
    with pytest.raises(ValueError, match="unknown arrival shape"):
        arrivals({"shape": "burst", "offset_s": 1.0}, 100, 2, 12.0)
