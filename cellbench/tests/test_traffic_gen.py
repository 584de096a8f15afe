import hashlib
import json
import os

import numpy as np
import pytest

from cellbench import traffic_gen as tg

HERE = os.path.dirname(os.path.abspath(__file__))


def mix(name):
    with open(os.path.join(HERE, "..", "traffic", f"{name}.json")) as f:
        return json.load(f)


def sizes(plan):
    return sorted((len(i.prompt), i.max_new) for i in plan.items)


def test_open_loop_is_a_function_of_the_seed():
    a = tg.make_plan(mix("chat"), {"rate": 2.0}, 7, 1000, 60.0)
    b = tg.make_plan(mix("chat"), {"rate": 2.0}, 7, 1000, 60.0)
    assert [(i.prompt, i.max_new, i.due) for i in a.items] == [
        (i.prompt, i.max_new, i.due) for i in b.items]


def test_every_seed_gets_the_same_sizes_and_gaps_in_another_order():
    a = tg.make_plan(mix("chat"), {"rate": 2.0}, 1, 1000, 60.0)
    b = tg.make_plan(mix("chat"), {"rate": 2.0}, 2 ** 31 + 5, 1000, 60.0)
    assert sorted(len(i.prompt) for i in a.items) == sorted(
        len(i.prompt) for i in b.items)
    assert sorted(i.max_new for i in a.items) == sorted(
        i.max_new for i in b.items)
    assert [i.due for i in a.items] != [i.due for i in b.items]
    ga = np.diff([0.0] + [i.due for i in a.items])
    gb = np.diff([0.0] + [i.due for i in b.items])
    np.testing.assert_allclose(sorted(ga), sorted(gb))
    assert abs(ga.mean() - 0.5) < 1e-9            # 1 / rate, exactly


def test_lengths_follow_the_stated_distribution():
    spec = mix("chat")["prompt"]
    xs = tg.length_set(spec, 1001)
    assert xs.min() >= spec["min"] and xs.max() <= spec["max"]
    assert abs(np.median(xs) - spec["median"]) <= 2
    u = tg.length_set(mix("longprompt")["prompt"], 100)
    assert u.min() >= 1024 and u.max() <= 2048


def test_gamma_gaps_have_the_stated_burstiness():
    g = tg.gap_set({"process": "gamma", "cv": 3.0}, 20000, 4.0)
    assert abs(g.mean() - 0.25) < 1e-9
    assert 2.4 < g.std() / g.mean() < 3.3
    p = tg.gap_set({"process": "poisson"}, 20000, 4.0)
    assert 0.95 < p.std() / p.mean() < 1.02


def test_closed_loop_sends_the_next_when_the_last_completes():
    plan = tg.make_plan(mix("batch"), {"clients": 3, "rounds": 4}, 11,
                        1000, 60.0)
    plan.start(100.0)
    first = plan.due(100.0)
    assert [i.client for i in first] == [0, 1, 2]
    assert all(i.due == 100.0 for i in first)
    assert plan.due(101.0) == []
    plan.on_finish(first[1], 105.5)
    nxt = plan.due(105.6)
    assert len(nxt) == 1 and nxt[0].client == 1 and nxt[0].due == 105.5
    assert nxt[0].index != first[1].index


def walk(plan, n):
    """The first ``n`` requests of a closed plan whose callers finish
    in the order they were served."""
    plan.start(0.0)
    out, ready = [], plan.due(0.0)
    while len(out) < n:
        item = ready.pop(0)
        out.append(item)
        plan.on_finish(item, float(len(out)))
        ready += plan.due(float(len(out)))
    return out


def batch_cell_plan(seed):
    return tg.make_plan(mix("batch"), {"clients": 12, "rounds": 12},
                        seed, 151936, 50.0)


@pytest.mark.parametrize("seed,digest", [
    (7, "8286e92339e80adc8c8bce49465e80c61e7633cc62ccb7093dc0d63212fb559c"),
    (2 ** 31 + 26,
     "120128532de46eb9cdd24825151f212a0c449b4ca3533a32bf4d5278e433506b"),
])
def test_the_first_block_is_what_it_was_before_the_plan_could_extend(
        seed, digest):
    """Recorded from PR 24's generator (which wrapped after clients x
    rounds = 144 requests): every reading taken under it still stands
    for the same requests."""
    items = walk(batch_cell_plan(seed), 144)
    text = json.dumps([[i.index, i.client, i.max_new, i.prompt]
                       for i in items])
    assert hashlib.sha256(text.encode()).hexdigest() == digest


def test_a_closed_plan_extends_and_never_hands_a_request_out_twice():
    plan = batch_cell_plan(11)
    items = walk(plan, 400)
    assert plan.wrapped == 0
    assert len({i.index for i in items}) == 400
    assert len({tuple(i.prompt) for i in items}) == 400
    # a further block is stratified like the first: the same lengths
    assert sorted(len(i.prompt) for i in items[144:288]) == sorted(
        len(i.prompt) for i in items[:144])
    assert all(i.client == items[k % 12].client
               for k, i in enumerate(items))


def test_a_plan_says_what_it_can_draw():
    """All the harness reads of a mix: the range of prompts (the
    prefill buckets to warm) and the longest answer and request (the
    reference's padding)."""
    chat = tg.make_plan(mix("chat"), {"rate": 2.0}, 3, 1000, 10.0)
    assert chat.prompt_range == (32, 2000)
    assert chat.output_max == 512 and chat.total_max == 2512
    batch = tg.make_plan(mix("batch"), {"clients": 3, "rounds": 2}, 3,
                         1000, 10.0)
    assert batch.prompt_range == (32, 2000) and batch.total_max == 2768
    assert all(32 <= len(i.prompt) <= 2000 and i.max_new <= 512
               for i in chat.items)
