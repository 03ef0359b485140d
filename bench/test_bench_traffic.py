"""The traffic generator: a function of the seed, the same work for every
seed, and data files that cannot run code."""
import collections
import json
from pathlib import Path

import numpy as np
import pytest

from bench.lib import exprs
from bench.lib import traffic as TR

MIXES = Path(__file__).parent / "traffic"
DASH = json.loads((MIXES / "dash.json").read_text())
REPORT = json.loads((MIXES / "report.json").read_text())
SEED = 2**31 + 17


def counts(panels):
    return collections.Counter(p["template"] for p in panels)


def test_open_schedule_is_a_function_of_the_seed():
    a = TR.open_schedule(DASH, 51, SEED)
    assert a == TR.open_schedule(DASH, 51, SEED)
    assert a != TR.open_schedule(DASH, 51, SEED + 1)


def test_every_seed_gets_the_same_work_in_another_order():
    a = TR.open_schedule(DASH, 51, SEED)
    b = TR.open_schedule(DASH, 51, 3)
    assert counts(a) == counts(b)
    n = round(DASH["rate_per_s"] * 51)
    assert len(a) == n
    want = {t["name"]: t["share"] * n for t in DASH["templates"]}
    assert all(abs(counts(a)[k] - v) < 1 for k, v in want.items())
    gaps = [np.diff([p["due"] for p in s] + [51.0]) for s in (a, b)]
    assert np.allclose(np.sort(gaps[0]), np.sort(gaps[1]))
    assert not np.allclose(gaps[0], gaps[1])
    assert all(0 <= p["due"] < 51 for p in a)


def test_parameters_stay_in_their_ranges():
    for p in TR.open_schedule(DASH, 51, SEED):
        lo, hi = p["slots"][0]["ranges"]["l_shipdate"]
        if p["template"] == "q6_day":
            assert hi - lo == 1 and 366 <= lo <= 2191
        elif p["template"] == "q1":
            assert lo == -np.inf and 2527 - 120 <= hi <= 2527 - 60
            assert [s["expr"] for s in p["slots"]] == [
                "count", "sum_qty", "sum_base_price", "sum_disc_price",
                "sum_charge"]
            assert all(s["group"] == "rfls" for s in p["slots"])
        else:
            assert hi - lo in (365, 366)
            dlo, dhi = p["slots"][0]["ranges"]["l_discount"]
            assert 0.004 < dlo < dhi < 0.106 and dhi - dlo == pytest.approx(
                0.03)


def test_closed_streams_keep_the_shares_at_every_step():
    a = TR.closed_streams(REPORT, SEED, 64)
    assert a == TR.closed_streams(REPORT, SEED, 64)
    assert a != TR.closed_streams(REPORT, SEED + 1, 64)
    assert len(a) == REPORT["clients"]
    share = {t["name"]: t["share"] for t in REPORT["templates"]}
    for k in range(64):
        now = collections.Counter(s[k]["template"] for s in a)
        assert {n: c / len(a) for n, c in now.items()} == share
    assert counts(a[0]) == {"q1": 32, "q6": 32}


def test_boundaries_hold_every_bound_as_float32():
    panels = TR.open_schedule(DASH, 51, SEED)
    b = TR.boundaries(panels)
    for p in panels:
        for col, (lo, hi) in p["slots"][0]["ranges"].items():
            for x in (lo, hi):
                assert float(np.float32(x)) in set(b[col].tolist())


@pytest.mark.parametrize("text", ["__import__('os')", "x.real", "f(x)",
                                  "x[y]", "'a'", "lambda: 1", "x if y else z"])
def test_expressions_refuse_anything_but_arithmetic(text):
    with pytest.raises(ValueError):
        exprs.parse(text)


def test_expressions_evaluate_with_the_operands_types():
    f = exprs.parse("a * (1 - b) + P[1] / 2 - -inf")
    assert f({"a": 2.0, "b": 0.5, "P": [0, 4]}) == np.inf
    v = exprs.parse("a * (1 - b)")({"a": np.float32([3]), "b": np.float32(
        [0.5])})
    assert v.dtype == np.float32 and v[0] == 1.5
    assert exprs.names("l_x * (1 + l_y) - inf") == {"l_x", "l_y"}
