"""The traffic generator: what a seed may change and what it may not."""

import copy
from collections import Counter

import pytest

from benchmarks.harness import schedule, spec


@pytest.fixture(scope="module")
def chat():
    return spec.traffic("chat-small")


def test_schedule_is_identical_whatever_the_seed(chat):
    """The schedule takes no --seed at all: two runs replay the same bytes."""
    a = schedule.open_loop_schedule(chat, 40)
    b = schedule.open_loop_schedule(copy.deepcopy(chat), 40)
    assert schedule.schedule_bytes(a) == schedule.schedule_bytes(b)


def test_schedule_changes_with_schedule_seed(chat):
    other = {**chat, "schedule_seed": chat["schedule_seed"] + 1}
    assert schedule.schedule_bytes(schedule.open_loop_schedule(chat, 40)) \
        != schedule.schedule_bytes(schedule.open_loop_schedule(other, 40))


@pytest.mark.parametrize("seconds", [10, 40, 51])
def test_counted_requests_are_rate_times_seconds(chat, seconds):
    plan = schedule.open_loop_schedule(chat, seconds)
    counted = [a for a in plan if a.counted]
    assert len(counted) == round(chat["rate_rps"] * seconds)
    lo = chat["warmup_s"]
    assert all(lo <= a.due_s < lo + seconds for a in counted)
    assert all(not a.counted for a in plan
               if not lo <= a.due_s < lo + seconds)
    assert [a.due_s for a in plan] == sorted(a.due_s for a in plan)


def test_one_arrival_in_each_interval(chat):
    plan = [a for a in schedule.open_loop_schedule(chat, 40) if a.counted]
    step = 1.0 / chat["rate_rps"]
    for i, a in enumerate(plan):
        assert i * step <= a.due_s - chat["warmup_s"] < (i + 1) * step


@pytest.mark.parametrize("which", ["prompt_len", "output_len"])
def test_lengths_are_the_stated_quantiles(chat, which):
    plan = [a for a in schedule.open_loop_schedule(chat, 40) if a.counted]
    got = Counter(getattr(a, which) for a in plan)
    want = Counter(schedule.quantile_lengths(chat[which], len(plan)))
    assert got == want
    spec_ = chat[which]
    assert min(got) >= spec_["min"] and max(got) <= spec_["max"]
    ordered = sorted(got.elements())
    assert abs(ordered[len(ordered) // 2] - spec_["median"]) \
        <= 0.05 * spec_["median"]


def test_the_window_does_not_depend_on_a_traced_lead(chat):
    """A traced run sends more uncounted requests first; what it counts is
    the same schedule, later."""
    lead = chat["warmup_s"] + 3.0
    plain = [a for a in schedule.open_loop_schedule(chat, 40) if a.counted]
    traced = [a for a in schedule.open_loop_schedule(
        {**chat, "warmup_s": lead}, 40) if a.counted]
    assert [(round(a.due_s - chat["warmup_s"], 9), a.prompt_len,
             a.output_len) for a in plain] \
        == [(round(a.due_s - lead, 9), a.prompt_len, a.output_len)
            for a in traced]


def test_seed_changes_token_ids_only(chat):
    plan = schedule.open_loop_schedule(chat, 10)
    lens = [a.prompt_len for a in plan]
    a = schedule.token_ids(1, lens, (0, 50257))
    b = schedule.token_ids(2**31 + 12345, lens, (0, 50257))
    assert [len(x) for x in a] == [len(x) for x in b] == lens
    assert a != b
    assert a == schedule.token_ids(1, lens, (0, 50257))
    assert all(0 <= t < 50257 for x in b for t in x)


def test_no_request_passes_the_positions_it_is_served_at():
    """A request of ``p`` prompt tokens and ``o`` answer tokens is served
    whole where ``p + o <= max_len``: its last token is handed out when the
    slot holds ``p + o - 1`` positions, and the scheduler ends a request
    only once ``lengths + 1 >= max_len`` (``_should_evict``).  The longest
    a mix can draw reaches that edge in two cells (33,792 of 33,792; 8,192
    of 8,192, which that cell's pool does hold and its runs serve with none
    failed); an earlier form of this case asked for one position more than
    the scheduler does and failed on every tree."""
    man = spec.manifest()
    for cell in man["workloads"]:
        tr = spec.traffic(cell["traffic"])
        if tr["kind"] == "train_steps":
            continue
        cfg = spec.config(man, cell["config"])
        top = schedule.reach(tr)["max_total"]
        assert top <= cfg["serve"]["max_len"], cell["name"]
        if tr["kind"] == "backlog":
            assert max(p + o for p, o in schedule.backlog_lengths(tr)) \
                <= top, cell["name"]


def test_backlog_pool_is_fixed():
    tr = spec.traffic("batch")
    assert schedule.backlog_lengths(tr) == schedule.backlog_lengths(tr)
    assert len(schedule.backlog_lengths(tr)) == tr["pool_requests"]
