"""The traffic generator: one seed, one schedule; every seed, the same
work in another order."""

import collections

import numpy as np

import cells  # noqa: F401  (puts bench/ on the path)
from generator import load_mix, schedule


def _flat(sched):
    return [(t.due_s, t.session, t.new_tokens, t.prompt.tobytes())
            for t in sched.warmup + sched.window]


def test_one_seed_gives_one_schedule():
    mix = load_mix("chat-swap")
    a = schedule(mix, 2**31 + 11, 51, 92544, 1024)
    b = schedule(mix, 2**31 + 11, 51, 92544, 1024)
    assert _flat(a) == _flat(b)
    c = schedule(mix, 2**31 + 12, 51, 92544, 1024)
    assert _flat(a) != _flat(c)


def test_every_seed_gets_the_same_work():
    mix = load_mix("chat-swap")
    rate = mix["knee_req_s"] * mix["phases"][0]["x_knee"]
    block = mix["block"]
    for seed in (1, 2, 2**33 + 5):
        s = schedule(mix, seed, 51, 92544, 1024)
        n = len(s.window)
        # each block spans block / rate seconds
        assert abs(n - round(51 * rate)) <= block
        full = (n // block) * block
        lens = [t.new_tokens for t in s.window[:full]]
        for b in range(0, full, block):
            assert sorted(lens[b:b + block]) == sorted(lens[:block])
        assert all(8 <= x <= 24 for x in lens)
        due = [t.due_s for t in s.window]
        assert due == sorted(due) and due[-1] < 51
        assert len(s.warmup) == mix["live_sessions"] + 1
        assert s.warmup[-1].session == s.warmup[0].session
        # each full block opens block / turns_mean fresh sessions
        seen = {t.session for t in s.warmup}
        opened = []
        for t in s.window:
            opened.append(t.session not in seen)
            seen.add(t.session)
        for b in range(0, full, block):
            assert sum(opened[b:b + block]) == block // mix["turns_mean"]


def test_sessions_stay_inside_the_cache():
    mix = load_mix("chat-swap")
    s = schedule(mix, 7, 51, 92544, 1024)
    history = collections.defaultdict(int)
    for t in s.warmup + s.window:
        if not history[t.session]:
            history[t.session] = len(t.prompt)
        history[t.session] += t.new_tokens
        assert history[t.session] < 1024 - 1
    lens = {len(t.prompt) for t in s.warmup}
    assert lens == {256, 512}


def test_bursts_follow_the_phases():
    mix = load_mix("chat-burst")
    s = schedule(mix, 3, 48, 50304, 1024)
    due = np.array([t.due_s for t in s.window])
    cycle = sum(p["seconds"] for p in mix["phases"])
    in_burst = (due % cycle) >= mix["phases"][0]["seconds"]
    knee = mix["knee_req_s"]
    calm, burst = (p["x_knee"] * knee for p in mix["phases"])
    rate_burst = in_burst.sum() / (48 / cycle * mix["phases"][1]["seconds"])
    rate_calm = (~in_burst).sum() / (48 / cycle * mix["phases"][0]["seconds"])
    assert abs(rate_burst - burst) / burst < 0.25
    assert abs(rate_calm - calm) / calm < 0.25
