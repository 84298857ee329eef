"""The traffic generator's parameters, the admission buckets set-up
warms, and the parts of the output check that need no model."""
import statistics

import pytest

from iolmbench import check, spec, traffic
from iolmbench.serve import Served, require

SCAN = spec.load_json(f"{spec.BENCH_DIR}/traffic/scan.json")


def _mix(**kw):
    m = dict(SCAN, table_rows=2000)
    m.update(kw)
    return m


OPEN = {"kind": "open_loop", "rate_per_s": 5.0, "rows_per_request": [1, 8],
        "schedule_seed": 21}


def test_backlog_is_one_request_for_the_whole_column():
    arr = traffic.schedule(_mix(), 2 ** 31 + 11, 40.0)
    assert len(arr) == 1 and arr[0].due_s == 0.0
    assert arr[0].rows == tuple(range(2000))


def test_open_loop_offers_the_same_work_on_every_seed():
    m = _mix(arrivals=OPEN)
    a = traffic.schedule(m, 3, 20.0)
    b = traffic.schedule(m, 2 ** 33 + 5, 20.0)
    assert len(a) == len(b) == 100
    assert all(0.0 < x.due_s < 20.0 for x in a + b)
    assert sorted(len(x.rows) for x in a) == sorted(len(x.rows) for x in b)
    assert [len(x.rows) for x in a] != [len(x.rows) for x in b]
    assert a[0].rows[0] == SCAN["setup_rows"]          # after set-up's rows
    assert a[-1].rows[-1] == SCAN["setup_rows"] + sum(
        len(x.rows) for x in a) - 1


@pytest.mark.parametrize("cv", [0.5, 3.0])
def test_gap_cv_sets_the_burstiness(cv):
    def spread(c):
        arr = traffic.schedule(_mix(arrivals=dict(OPEN, gap_cv=c),
                                    table_rows=20000), 7, 200.0)
        gaps = [y.due_s - x.due_s for x, y in zip(arr, arr[1:])]
        return statistics.pstdev(gaps) / statistics.mean(gaps)

    assert spread(cv) == pytest.approx(cv, rel=0.3)
    assert (spread(cv) > spread(1.0)) == (cv > 1.0)


def test_zipf_values_repeat_in_one_pattern_for_every_seed():
    m = _mix(values={"distinct": 50, "zipf": 1.1})
    a, b = traffic.review_column(m, 1), traffic.review_column(m, 2)
    assert len(a) == 2000 and len(set(a)) <= 50 and len(set(b)) <= 50

    def pattern(col):                      # each row -> its value's first row
        first = {}
        return [first.setdefault(v, i) for i, v in enumerate(col)]

    assert pattern(a) == pattern(b)
    assert set(a).isdisjoint(b)                       # the seed sets text
    top = max(set(a), key=a.count)
    assert a.count(top) > 2000 / 50 * 3               # a heavy head


def test_distinct_rows_without_values():
    col = traffic.review_column(_mix(), 9)
    assert len(set(col)) == len(col)
    lo, hi = SCAN["text_bytes"]
    assert all(lo <= len(v) <= hi for v in col)


def test_buckets_set_up_warms():
    instr = SCAN["instruction"]                       # 103 bytes
    col = traffic.review_column(_mix(), 4)
    assert traffic.longest_per_bucket(col, instr, True, (64, 128, 256),
                                      288) == {128: 127}
    # whole prompts: 104 + 128 tokens
    assert traffic.longest_per_bucket(col, instr, False, (64, 128, 256),
                                      288) == {256: 127}
    # 40-150 bytes: 64- and 128-token suffixes, and the rows whose split
    # would not fit below max_len prefilled whole in the top bucket
    wide = traffic.review_column(_mix(text_bytes=[40, 150]), 4)
    assert traffic.longest_per_bucket(wide, instr, True, (64, 128, 256),
                                      288) == {64: 63, 128: 127, 256: 150}


def test_recipe_bits():
    assert check.recipe_bits({"name": "base"}) == 16
    assert check.recipe_bits({"name": "w8-absmax", "wbits": 8,
                              "quant_method": "absmax"}) == 8
    assert check.recipe_bits({"name": "bs128-d75", "block_bs": 128,
                              "block_density": 0.75}) is None
    assert check.recipe_bits({"name": "w8-gptq", "wbits": 8,
                              "quant_method": "gptq"}) is None


def test_repeated_prompts_are_checked_exactly():
    rows = [Served("p", [5, 6], "ab"), Served("q", [7], "c"),
            Served("p", [5, 6], "ab"), Served("p", [9], "zz")]
    assert check.distinct(rows) == rows[:2]
    assert check.differing(rows) == 1


def test_harness_refuses_an_engine_without_what_it_reads():
    class Engine:
        _active = {}

    with pytest.raises(RuntimeError, match="_cur_pos"):
        require(Engine(), ("_active", "_cur_pos"))
