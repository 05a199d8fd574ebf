"""Kernel work counts against hand counts at small shapes."""
import pytest

from chip_tiny import layout, tiny_cell

subround = layout.kernel_counter("subround")
cms = layout.kernel_counter("cms")
hot_gather = layout.kernel_counter("hot_gather")


def test_subround_bytes_by_hand():
    # B=2 lanes, C=1 entry, S=1 slot, F=1, J=1:
    # lanes in 2*(16+44)=120, out 2*16=32; tables in 16+12+24+12+16+4+4=88;
    # tables out 12+24+12+16+4+8+24+12=112
    assert subround.per_call(2, 1, 1, 1, 1) == 120 + 32 + 88 + 112


def test_cms_bytes_by_hand():
    # 3 lanes x (16 B hash + 4 B mask + 4 B estimate) + a [5, 8] sketch in and out
    assert cms.per_call(3, 5, 8) == 3 * 24 + 2 * 5 * 8 * 4


def test_hot_gather_by_hand():
    # [2, 3] x [3, 1]: 2*2*3*1 ops; ids 2, hot 3, rows 3, out 2, hit 2 (int32)
    assert hot_gather.per_call(2, 3, 1) == (12, 4 * (2 + 3 + 3 + 2 + 2))


@pytest.mark.parametrize("traffic", ["ladder12", "hotin_churn"])
def test_per_window_follows_the_cell(traffic):
    cell = tiny_cell("paper_rack_orbitcache", traffic)
    sh = layout.shapes(cell)
    # 64 client + 64 correction + 4 servers x 10 replies + 32 fetch lanes over 4 subrounds
    assert sh["lanes"] == (64 + 64 + 40 + 32) // 4
    w = subround.per_window(sh)
    assert w["bytes"] == 4 * 2 * subround.per_call(sh["lanes"], 16, 8, 1, 8)
    churn = traffic == "hotin_churn"
    assert (cms.per_window(sh) is not None) == churn
    assert (hot_gather.per_window(sh) is not None) == churn
    nocache = layout.shapes(tiny_cell("paper_rack_nocache", traffic))
    assert subround.per_window(nocache) is None
