import pytest

from perfbench.harness import roofline


def test_the_count_for_a_rung_follows_from_its_shape():
    per_vote = (265 + 252 * 8 + 128 * 8 + 267) * 2 * 32 * 32
    assert roofline.FIELD_MULS_PER_VERIFY == 3572
    assert roofline.rung_ops(4096) == 4096 * per_vote
    assert roofline.rung_ops(64) * 64 == roofline.rung_ops(4096)
    assert roofline.rung_bytes(4096, 4096) == 4096 * 177 + 4096 * 12


def test_a_rung_is_bound_by_the_integer_peak():
    least, bound = roofline.least_seconds(4096, 4096, "TPU v5 lite")
    assert bound == "int8_ops_per_s"
    assert least == pytest.approx(roofline.rung_ops(4096) / 393e12)
    # a step that took exactly the least time reads 100%
    assert roofline.roofline_share(4096, 4096, least, "TPU v5 lite") == pytest.approx(100.0)
    assert roofline.roofline_share(4096, 4096, 10 * least, "TPU v5 lite") == pytest.approx(10.0)


def test_a_device_kind_not_in_the_table_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
    assert "source" in __import__("json").load(open(roofline._PEAKS))
