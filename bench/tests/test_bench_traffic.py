"""The traffic generator: a pure function of the seed, the same work for
every seed, lateness counted from the due time."""
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import traffic_gen  # noqa: E402


def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as fh:
        return json.load(fh)


def test_same_seed_same_schedule():
    mix = _mix("online_longprompt")
    a = traffic_gen.schedule(mix, 2**31 + 123, 152064, 40)
    b = traffic_gen.schedule(mix, 2**31 + 123, 152064, 40)
    assert a == b
    c = traffic_gen.schedule(mix, 2**31 + 124, 152064, 40)
    assert [r.prompt for r in a] != [r.prompt for r in c]


def test_every_seed_gets_the_same_sizes_and_gaps():
    mix = _mix("online_longprompt")
    runs = [traffic_gen.schedule(mix, s, 152064, 40) for s in (1, 2**32 + 9)]
    sizes = [sorted(len(r.prompt) for r in run) for run in runs]
    outs = [sorted(r.max_new_tokens for r in run) for run in runs]
    gaps = [sorted(np.diff([r.due_s for r in run])) for run in runs]
    assert sizes[0] == sizes[1] and outs[0] == outs[1]
    # the first gap of each order is left out; the others are shared
    assert len(set(np.round(gaps[0], 9)) ^ set(np.round(gaps[1], 9))) <= 2
    assert {len(r.prompt) for r in runs[0]} <= set(
        traffic_gen.prompt_sizes(mix))


def test_lengths_and_rate_follow_the_file():
    mix = _mix("online_longprompt")
    run = traffic_gen.schedule(mix, 5, 152064, 40)
    assert len(run) == traffic_gen.request_count(mix, 40)
    assert all(mix["prompt"]["min"] <= len(r.prompt) <= mix["prompt"]["max"]
               for r in run)
    assert all(3 <= t < 152064 for r in run for t in r.prompt)
    rate = len(run) / run[-1].due_s
    assert abs(rate - mix["rate_per_s"]) / mix["rate_per_s"] < 0.05
    backlog = traffic_gen.schedule(_mix("offline_decode"), 5, 152064, 40)
    assert all(r.due_s == 0.0 for r in backlog)


def test_lateness_is_measured_from_the_due_time():
    late = traffic_gen.lateness([10.0, 11.0, 12.0], [10.0, 11.5, 12.25])
    assert late == {"count": 3, "p50_s": 0.25, "max_s": 0.5}
    early = traffic_gen.lateness([10.0], [9.0])
    assert early["max_s"] == 0.0


def test_every_seed_has_the_same_work_in_the_window():
    mix = _mix("online_longprompt")
    seconds = 40
    due_in = []
    for seed in (3, 2**31 + 5, 2**33 + 1):
        run = traffic_gen.schedule(mix, seed, 152064, seconds)
        win = [r for r in run if r.due_s < seconds]
        due_in.append((len(win), sorted(len(r.prompt) for r in win),
                       sorted(r.max_new_tokens for r in win)))
    assert len(set(map(str, due_in))) == 1
    assert due_in[0][0] == round(mix["rate_per_s"] * seconds)
    backlog = _mix("offline_decode")
    runs = [traffic_gen.schedule(backlog, s, 152064, seconds) for s in (1, 2)]
    first = [sorted(len(r.prompt) for r in run[:backlog["sizes"]])
             for run in runs]
    assert first[0] == first[1] == traffic_gen.prompt_sizes(backlog)


def test_a_block_keeps_the_tail_of_the_prompt_lengths():
    """A window of fewer requests than sizes picks them evenly, so the
    longest prompts still come; one of more repeats each size about as
    often."""
    mix = dict(_mix("online_longprompt"), rate_per_s=0.4)
    sizes = traffic_gen.prompt_sizes(mix)
    win = [len(r.prompt) for r in traffic_gen.schedule(mix, 9, 152064, 40)
           if r.due_s < 40]
    assert len(win) == 16 and max(win) == sizes[-1] and min(win) == sizes[0]
    big = dict(mix, rate_per_s=1.2)
    win = [len(r.prompt) for r in traffic_gen.schedule(big, 9, 152064, 40)
           if r.due_s < 40]
    grid = traffic_gen._lognormal_quantiles(mix["prompt"], mix["sizes"])
    assert len(win) == 48 and sorted(win) == sorted(np.repeat(grid, 2))
