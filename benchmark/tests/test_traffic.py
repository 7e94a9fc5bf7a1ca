"""The arrival schedule and the lengths are a pure function of --seed, and
every seed offers the same multiset of work."""

import collections

from benchlib import files, trafficgen as tg

BIG = 3_000_000_011          # more than 32 signed bits hold


def _mix(name):
    return files.load_json(f"{files.BENCH_DIR}/traffic/{name}.json")


def _key(plan):
    return [(r.idx, r.prompt, r.max_tokens, r.due_s) for r in plan.requests]


def test_plans_are_pure_functions_of_the_seed():
    for name in ("decode-closed", "chat-open"):
        a = tg.make_plan(_mix(name), BIG, 10, 32)
        b = tg.make_plan(_mix(name), BIG, 10, 32)
        c = tg.make_plan(_mix(name), BIG + 1, 10, 32)
        assert _key(a) == _key(b)
        assert _key(a) != _key(c)


def test_every_seed_gets_the_same_sizes_in_another_order():
    mix = _mix("chat-open")
    a = tg.sized_requests(mix, 1, 256)
    b = tg.sized_requests(mix, BIG, 256)
    assert a != b
    for col in (0, 1):
        for blk in range(4):
            sa = collections.Counter(x[col] for x in a[blk * 64:(blk + 1) * 64])
            sb = collections.Counter(x[col] for x in b[blk * 64:(blk + 1) * 64])
            assert sa == sb
    p = [x[0] for x in a]
    assert min(p) >= 32 and max(p) <= 1536
    assert 200 <= sorted(p)[len(p) // 2] <= 320      # median ~256


def test_open_loop_blocks_span_exactly_block_over_rate():
    mix = _mix("chat-open")
    plan = tg.make_plan(mix, 5, 10, 32)
    due = [r.due_s for r in plan.requests]
    assert due == sorted(due)
    assert abs(due[63] - 64 / mix["rate"]) < 1e-9
    assert abs(due[127] - 128 / mix["rate"]) < 1e-9


def test_closed_loop_sizes_and_clients():
    plan = tg.make_plan(_mix("decode-closed"), 9, 10, 16)
    assert plan.loop == "closed" and plan.clients == 16
    later = plan.requests[16:]
    assert all(256 <= len(r.prompt) <= 1024 for r in later)
    assert all(256 <= r.max_tokens <= 512 for r in later)
    assert all(len(r.prompt) + r.max_tokens <= 1536 for r in plan.requests)
    # the clients' first outputs are spread so they fall out of step
    firsts = sorted(r.max_tokens for r in plan.requests[:16])
    assert firsts[0] < 64 and firsts[-1] > 450


def test_prompts_are_printable_ascii_and_share_no_prefix():
    ps = [tg.prompt_text(BIG, i, 200) for i in range(50)]
    assert all(all(0x20 <= ord(c) < 0x7f for c in p) for p in ps)
    assert len({p[:8] for p in ps}) == 50
