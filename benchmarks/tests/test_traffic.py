"""The traffic generator: the same seed gives the same inputs, and every
seed offers the same lengths in another order."""
import numpy as np

from benchmarks.harness import traffic

MIX = {"strata": 4, "prompt_tokens": {"min": 32, "max": 128},
       "output_tokens": {"min": 64, "max": 192}}


def test_length_grid_is_log_uniform_midpoints():
    assert traffic.length_grid({"min": 32, "max": 128}, 4) == [38, 54, 76, 108]


MIX["callers"] = 8


def test_same_seed_same_requests_and_big_seeds_work():
    seed = 2 ** 31 + 12345
    a = traffic.CallerStream(MIX, 1000, seed, 0)
    b = traffic.CallerStream(MIX, 1000, seed, 0)
    for _ in range(9):
        pa, na = a.next()
        pb, nb = b.next()
        assert na == nb and np.array_equal(pa, pb)


def test_every_seed_offers_the_same_lengths_and_other_tokens():
    grid_p = traffic.length_grid(MIX["prompt_tokens"], 4)
    grid_o = traffic.length_grid(MIX["output_tokens"], 4)
    every = sorted((p, o) for p in grid_p for o in grid_o)
    cycles, firsts = set(), set()
    for seed in range(1, 5):
        s = traffic.CallerStream(MIX, 1000, seed, 5)
        firsts.add(tuple(s.next()[0]))
        s = traffic.CallerStream(MIX, 1000, seed, 5)
        cycle = [s.lengths() for _ in range(16)]
        assert sorted(cycle) == every          # each pair once a cycle
        cycles.add(tuple(cycle))
    assert len(cycles) == 1 and len(firsts) == 4


def test_callers_start_spread_over_the_cycle_and_over_their_phases():
    streams = [traffic.CallerStream(MIX, 1000, 3, c) for c in range(8)]
    assert len({s.at % 16 for s in streams}) == 8
    assert [round(s.first_share, 4) for s in streams] == [
        round((c + 0.5) / 8, 4) for c in range(8)]
    prompt, n_out = streams[0].first_ramp()
    assert 1 <= n_out <= 192 // 8


def test_cycle_start_gives_another_schedule_of_the_same_pairs():
    base = traffic.CallerStream(MIX, 1000, 3, 2)
    turned = traffic.CallerStream(dict(MIX, cycle_start=5), 1000, 3, 2)
    a = [base.lengths() for _ in range(16)]
    b = [turned.lengths() for _ in range(16)]
    assert a != b and sorted(a) == sorted(b) and b == a[5:] + a[:5]


def test_a_sessions_context_opens_every_prompt_of_its_callers():
    mix = dict(MIX, sessions={"count": 4,
                              "context_tokens": {"min": 100, "max": 200}})
    contexts = traffic.session_contexts(mix, 1000, 9)
    assert [c.size for c in contexts] == traffic.length_grid(
        {"min": 100, "max": 200}, 4)
    assert traffic.session_contexts(MIX, 1000, 9) == []
    turns = set()
    for caller in (1, 5):                      # both talk in session 1
        s = traffic.CallerStream(mix, 1000, 9, caller)
        for _ in range(3):
            n_turn = s.lengths()[0]
            s.at -= 1
            prompt, _ = s.next()
            assert np.array_equal(prompt[:s.context.size], contexts[1])
            assert prompt.size == contexts[1].size + n_turn
            turns.add(tuple(prompt[s.context.size:]))
    assert len(turns) == 6                     # no turn is sent twice
    other = traffic.session_contexts(mix, 1000, 10)
    assert not np.array_equal(other[1], contexts[1])


def test_train_batches_differ_row_by_row_and_labels_are_next_tokens():
    it = traffic.train_batches({"batch": 4, "seq_len": 16}, 1000, 7)
    ids, labels = next(it)
    ids2, _ = next(it)
    assert ids.shape == labels.shape == (4, 16)
    assert np.array_equal(ids[:, 1:], labels[:, :-1])
    assert len({tuple(r) for r in np.concatenate([ids, ids2])}) == 8
