"""The lineitem generator follows dbgen's rules and is a function of the
seed (CPU, small table)."""
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench.tables import lineitem

CFG = json.loads((Path(__file__).parent / "configs"
                  / "tpch-lineitem-1chip.json").read_text())


def table(seed, rows=1 << 20):
    cfg = dict(CFG, rows=rows)
    sh = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    return {k: np.asarray(v).ravel()
            for k, v in lineitem.generate(cfg, seed, sh).items()}


@pytest.fixture(scope="module")
def cols():
    return table(2**31 + 5)


def test_q1_groups_have_dbgen_shares_with_nf_rare(cols):
    share = np.bincount(cols["rfls"], minlength=4) / cols["rfls"].size
    af, nf, no, rf = share
    # N|F: shipped on or before CURRENTDATE, received after it: about half
    # of the 30 days before it, out of ~2,400 ship days
    assert 0.003 < nf < 0.01
    assert abs(af - rf) < 0.01 and 0.22 < af < 0.27
    assert 0.47 < no < 0.52


def test_linestatus_follows_shipdate(cols):
    ship, g = cols["l_shipdate"], cols["rfls"]
    cur = CFG["dbgen"]["currentdate"]
    open_ = g == lineitem.FLAG_IDS[("N", "O")]
    assert np.all(ship[open_] > cur) and np.all(ship[~open_] <= cur)
    returned = np.isin(g, [lineitem.FLAG_IDS[("A", "F")],
                           lineitem.FLAG_IDS[("R", "F")]])
    assert np.all(ship[returned] < cur)   # received after shipping
    assert ship.min() >= 1 and ship.max() <= 2526


def test_extendedprice_is_quantity_times_retailprice(cols):
    q, ext = cols["l_quantity"], cols["l_extendedprice"]
    assert set(np.unique(q)) == set(range(1, 51))
    cents = ext.astype(np.float64) * 100 / q
    assert np.all(np.abs(cents - np.round(cents)) < 1e-2 * q)
    assert cents.min() >= 90000 - 1 and cents.max() <= 209900 + 1


def test_discount_and_tax_are_whole_hundredths(cols):
    for col, hi in (("l_discount", 10), ("l_tax", 8)):
        k = np.round(cols[col].astype(np.float64) * 100)
        assert set(np.unique(k)) == set(range(hi + 1))
        assert np.all(np.abs(cols[col] - k / 100) < 1e-8)


def test_table_is_a_function_of_the_seed():
    a, b = table(2**31 + 5, 1 << 16), table(2**31 + 5, 1 << 16)
    c, d = table(2**31 + 6, 1 << 16), table(2**32 + 2**31 + 5, 1 << 16)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    for other in (c, d):
        assert not np.array_equal(a["l_extendedprice"],
                                  other["l_extendedprice"])
