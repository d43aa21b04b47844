import json
import os

import numpy as np

from benchmarks.harness import traffic
from benchmarks.harness.cell import HERE


def mix(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def chat(rate=4.0):
    m = mix("chat-steady")
    m["arrivals"]["requests_per_second"] = m["arrivals"][
        "requests_per_second"] or rate
    return m


def test_same_seed_same_requests_other_seed_other_tokens():
    a = traffic.requests(chat(), 30, 5, 32768)
    b = traffic.requests(chat(), 30, 5, 32768)
    c = traffic.requests(chat(), 30, 2**31 + 77, 32768)
    assert [p.due_s for p in a] == [p.due_s for p in b]
    assert all(np.array_equal(x.prompt_ids, y.prompt_ids)
               for x, y in zip(a, b))
    # the schedule is the mix's, whatever the seed; the contents are not
    assert [(p.due_s, p.prompt_ids.size, p.max_new_tokens) for p in a] == \
           [(p.due_s, p.prompt_ids.size, p.max_new_tokens) for p in c]
    assert not np.array_equal(a[0].prompt_ids, c[0].prompt_ids)


def test_lengths_match_the_file():
    m = chat()
    plan = traffic.schedule(m, 200)
    prompts = np.asarray([p for _, p, _ in plan])
    news = np.asarray([n for _, _, n in plan])
    spec = m["prompt_tokens"]
    assert prompts.min() >= spec["min"] and prompts.max() <= spec["max"]
    assert abs(np.median(prompts) - spec["median"]) < 0.05 * spec["median"]
    # lognormal(384, 0.8) clipped to 32..2048 has a mean near 500
    assert 430 < prompts.mean() < 560
    spec = m["max_new_tokens"]
    assert news.min() >= spec["min"] and news.max() <= spec["max"]
    assert abs(np.median(news) - spec["median"]) < 0.05 * spec["median"]


def test_poisson_arrivals_match_the_rate():
    m = chat(5.0)
    m["arrivals"]["requests_per_second"] = 5.0
    due = np.asarray([d for d, _, _ in traffic.schedule(m, 100)])
    pre = m["preroll_s"]
    assert due.min() >= -pre and due.max() < 100
    assert np.all(np.diff(due) >= 0)
    assert abs(due.size / (100 + pre) - 5.0) < 0.25
    gaps = np.diff(due)
    # exponential gaps: coefficient of variation 1
    assert 0.85 < gaps.std() / gaps.mean() < 1.15


def test_backlog_is_all_due_before_the_window():
    m = mix("doc-backlog")
    plan = traffic.schedule(m, 30)
    assert len(plan) == round(4.0 * (30 + m["preroll_s"]))
    assert all(d == -m["preroll_s"] for d, _, _ in plan)
    prompts = np.asarray([p for _, p, _ in plan])
    assert prompts.min() >= 1024 and prompts.max() <= 3584
    assert abs(np.median(prompts) - 2048) < 100


def test_train_rows_all_differ_and_labels_are_shifted():
    ids, labels = traffic.train_batch(9, 0, 8, 64, 64000)
    ids2, _ = traffic.train_batch(9, 1, 8, 64, 64000)
    assert np.array_equal(ids[:, 1:], labels[:, :-1])
    assert len({row.tobytes() for row in ids}) == 8
    assert not np.array_equal(ids, ids2)
    again, _ = traffic.train_batch(9, 0, 8, 64, 64000)
    assert np.array_equal(ids, again)
