"""The traffic generator: the same seed gives the same run, every seed
replays the same sizes and arrivals with other token ids, lengths are the
stated ones."""

import json
from pathlib import Path

import numpy as np
import pytest

from benchmark.lib import traffic as tr

ROOT = Path(__file__).resolve().parents[2]
BIG = 2 ** 31 + 77                 # the driver's seeds pass 2**31


def _mix(name):
    return json.loads((ROOT / "benchmark" / "traffic"
                       / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["chat_open", "longdoc_closed"])
def test_serve_plan_is_deterministic_and_seeds_share_the_work(name):
    mix = _mix(name)
    a = tr.serve_plan(mix, BIG, 40, 32000)
    b = tr.serve_plan(mix, BIG, 40, 32000)
    c = tr.serve_plan(mix, 5, 40, 32000)
    assert [r.prompt for r in a.requests] == [r.prompt for r in b.requests]
    # every seed replays one sequence: the same sizes in the same places
    # (and the same arrivals), other token ids
    assert [(len(r.prompt), r.max_new_tokens) for r in a.requests] == \
        [(len(r.prompt), r.max_new_tokens) for r in c.requests]
    assert [r.prompt for r in a.requests] != [r.prompt for r in c.requests]
    if a.due_s is not None:
        assert (a.due_s == c.due_s).all()
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    alo, ahi = mix["answer_tokens"]["min"], mix["answer_tokens"]["max"]
    for r in a.requests:
        assert lo <= len(r.prompt) <= hi and alo <= r.max_new_tokens <= ahi
        assert 0 <= min(r.prompt) and max(r.prompt) < 32000
    assert tr.max_context(mix) == hi + ahi <= 4096


def test_the_long_documents_are_the_issues_sizes():
    mix = _mix("longdoc_closed")
    assert (mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]) == \
        (1024, 3840) and mix["clients"] == 6


def test_a_bad_range_is_refused():
    with pytest.raises(ValueError):
        tr.stratified({"min": 9, "max": 1}, 4, np.random.default_rng(0))


def test_lengths_follow_the_stated_log_uniform():
    mix = dict(_mix("chat_open"), rate_per_s=10.0)
    n = tr.n_requests(mix, 40)
    assert n == round(mix["rate_per_s"] * 40)
    prompts, answers = tr.request_sizes(mix, n)
    # log-uniform on [64, 1024]: the median is the geometric mean, 256,
    # and a quarter of the draws fall under 64 * 16 ** 0.25 = 128
    assert np.median(prompts) == pytest.approx(256, rel=0.1)
    assert np.mean(prompts < 128) == pytest.approx(0.25, abs=0.05)
    assert np.median(answers) == pytest.approx((32 * 256) ** 0.5, rel=0.12)
    # independent pairing: sizes barely correlate
    assert abs(np.corrcoef(np.log(prompts), np.log(answers))[0, 1]) < 0.3


def test_answers_follow_prompts_where_the_mix_says_so():
    mix = _mix("longdoc_closed")
    prompts, answers = tr.request_sizes(mix, 120)
    assert (np.diff(prompts) >= 0).all() and (np.diff(answers) >= 0).all()


def test_open_loop_arrivals():
    mix = dict(_mix("chat_open"), rate_per_s=10.0)
    a = tr.serve_plan(mix, 1, 40, 32000)
    n = len(a.requests)
    assert a.clients is None and len(a.due_s) == n
    assert a.due_s[0] == 0 and (np.diff(a.due_s) >= 0).all()
    assert a.due_s[-1] < 40
    gaps = np.diff(a.due_s)
    # Poisson: the gaps' standard deviation is about their mean, 1 / rate
    assert np.mean(gaps) == pytest.approx(1 / mix["rate_per_s"], rel=0.05)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.25)


def test_closed_loop_clients_split_the_requests():
    mix = _mix("longdoc_closed")
    plan = tr.serve_plan(mix, 3, 40, 32000)
    assert plan.due_s is None and len(plan.clients) == mix["clients"]
    sent = sorted(i for c in plan.clients for i in c)
    assert sent == list(range(len(plan.requests)))


def test_block_order_spreads_sizes_through_the_run():
    rng = np.random.default_rng(0)
    order = tr.block_order(64, 8, rng)
    assert sorted(order) == list(range(64))
    for k in range(0, 64, 8):           # one of each eighth in each block
        assert sorted(i // 8 for i in order[k:k + 8]) == list(range(8))


def test_train_batches():
    mix = _mix("pretrain_1k")
    a = tr.train_batch(mix, BIG, 0, 32, 50304)
    b = tr.train_batch(mix, BIG, 0, 32, 50304)
    c = tr.train_batch(mix, BIG, 1, 32, 50304)
    assert a["input_ids"].shape == (32, 1024) == a["labels"].shape
    assert (a["input_ids"] == b["input_ids"]).all()
    assert (a["input_ids"] != c["input_ids"]).any()
    assert (a["input_ids"][:, 1:] == a["labels"][:, :-1]).all()
    assert a["input_ids"].max() < 50304 and a["input_ids"].dtype == np.int32
