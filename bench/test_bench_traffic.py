"""The traffic generator and the window arithmetic (CPU, no model)."""
import json
import math
from pathlib import Path

import numpy as np
import pytest

from bench import traffic
from bench.window import Record, percentile, window_metrics

MIXES = sorted((Path(__file__).parent / "traffic").glob("*.json"))


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_mix_files_load_and_plan_within_clips(path):
    mix = traffic.load_mix(path)
    reqs = traffic.plan(mix, 40.0, 2**31 + 17, vocab=1000)
    assert len(reqs) == traffic.window_count(mix, 40.0)
    p, o = mix.prompt_len, mix.output_len
    for r in reqs:
        assert p["min"] <= len(r.prompt) <= p["max"]
        assert o["min"] <= r.max_new_tokens <= o["max"]
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 1000
        assert 0.0 <= r.arrival_s < 40.0
    arr = [r.arrival_s for r in reqs]
    assert arr == sorted(arr)
    if not mix.open_loop:
        assert set(arr) == {0.0}


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_same_seed_same_inputs_other_seed_same_schedule(path):
    mix = traffic.load_mix(path)
    a = traffic.plan(mix, 30.0, 5, vocab=500)
    b = traffic.plan(mix, 30.0, 5, vocab=500)
    c = traffic.plan(mix, 30.0, 6, vocab=500)
    assert [(r.arrival_s, r.max_new_tokens, r.prompt.tolist()) for r in a] \
        == [(r.arrival_s, r.max_new_tokens, r.prompt.tolist()) for r in b]
    # another seed: the same sizes and arrivals in the same order, other
    # token ids
    assert [(r.arrival_s, len(r.prompt), r.max_new_tokens) for r in a] \
        == [(r.arrival_s, len(r.prompt), r.max_new_tokens) for r in c]
    assert [x.prompt.tolist() for x in a] != [x.prompt.tolist() for x in c]
    assert traffic.plan(mix, 30.0, -3, vocab=500)  # any whole number


def test_lognormal_median_and_poisson_rate():
    mix = traffic.Mix(name="t", arrival="poisson", rate_per_s=50.0,
                      prompt_len={"dist": "lognormal", "median": 100,
                                  "sigma": 0.5, "min": 1, "max": 10**6},
                      output_len={"dist": "uniform", "min": 3, "max": 5})
    reqs = traffic.plan(mix, 100.0, 0, vocab=10)
    assert len(reqs) == 5000
    med = np.median([len(r.prompt) for r in reqs])
    assert 95 <= med <= 105
    assert {r.max_new_tokens for r in reqs} == {3, 4, 5}
    assert reqs[-1].arrival_s < 100.0 and reqs[-1].arrival_s > 95.0


def test_bad_mix_refused(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"arrival": "bursty", "prompt_len": {},
                             "output_len": {}}))
    with pytest.raises(ValueError):
        traffic.load_mix(p)


def test_percentile_nearest_rank_with_infinity():
    assert percentile([3, 1, 2, 4], 50) == 2
    assert percentile(list(range(1, 11)), 90) == 9
    assert percentile([1.0] * 9 + [math.inf], 95) == math.inf
    assert math.isnan(percentile([], 50))


def test_cut_requests_count_at_infinity_and_never_fail():
    ok = Record(0.0, 0.1, [0.5, 0.6, 0.7], 0, "ok")
    cut_before_first = Record(1.0, None, [], 10, "cut")
    cut_mid = Record(2.0, 2.0, [2.5, 2.7], 5, "cut")
    m = window_metrics([ok, cut_before_first, cut_mid], 10.0, open_loop=True)
    assert m["ttft_p90_s"] == math.inf           # one of three never came
    assert m["ttft_p50_s"] == pytest.approx(0.5)
    assert m["tpot_p95_s"] == math.inf            # the gap cut_mid still owed
    assert m["n_gaps"] == 4
    assert m["queue_wait_p50_s"] == pytest.approx(0.1)


def test_rate_is_window_tokens_over_window_length():
    recs = [Record(0.0, 0.0, [1.0, 2.0, 3.0, 11.0, 12.0], 0, "ok"),
            Record(0.0, 0.0, [4.0, 9.9, 10.0, 10.1], 0, "cut")]
    m = window_metrics(recs, 10.0, open_loop=False)
    assert m["output_tokens_per_s"] == pytest.approx(6 / 10.0)
    # a backlog's gaps end inside the window; its cut is the run's end
    assert m["n_gaps"] == 2 + 2
    assert m["tpot_p95_s"] == pytest.approx(5.9)
