"""What decides ``correct`` in a serving cell, without an engine: the
statistics of the pooled gaps against the limits a configuration names, on
made-up gaps, and which finished requests are compared, on made-up lists."""

import types

import numpy as np
import pytest

import cells
import manifest as M

closed_loop = M.load_driver("closed_loop")
SERVE = cells.by_driver("closed_loop")
LIMITS = {w: cells.find(w)["config_values"]["limits"] for w in SERVE}
# the cells whose configuration holds a percentile beside the maximum (none is named here)
PERCENTILES = {w: [k for k in LIMITS[w] if k.startswith("served_logit_gap_p")] for w in SERVE}
BY_PERCENTILE = [w for w in SERVE if PERCENTILES[w]]


def _gaps(case: str):
    """2,200 served tokens as a sound run of a model with sparse experts reads
    them: 1.1% near-ties under 0.01 and one token whose last expert flipped."""
    rng = np.random.default_rng(32)
    gaps = np.zeros(2200, np.float32)
    gaps[rng.choice(2200, 25, replace=False)] = rng.uniform(0.0, 0.01, 25)
    gaps[7] = 0.30
    if case == "degraded_everywhere":  # 5% of the tokens miss by 0.05-0.3, as a lower precision does
        gaps[rng.choice(2200, 110, replace=False)] = rng.uniform(0.05, 0.3, 110)
    elif case == "one_altered_token":
        gaps[1234] = 3.0
    return gaps


@pytest.mark.parametrize("case", ["sound", "degraded_everywhere", "one_altered_token"])
@pytest.mark.parametrize("workload", BY_PERCENTILE)
def test_each_statistic_fails_the_fault_it_is_there_for(workload, case):
    """The maximum lets the flipped expert's token pass and fails the altered
    one; the percentile fails the model that misses everywhere, which the
    maximum lets pass."""
    fails = {"sound": [], "degraded_everywhere": PERCENTILES[workload], "one_altered_token": ["served_logit_gap"]}[case]
    numbers, ok = closed_loop.judge(_gaps(case), LIMITS[workload])
    assert set(numbers) == set(LIMITS[workload]) > set(PERCENTILES[workload])
    assert [k for k, (value, limit) in numbers.items() if value > limit] == fails
    assert ok is (not fails)


def test_a_percentile_is_held_somewhere():
    assert BY_PERCENTILE, "no configuration's limits name a percentile of the gaps"


@pytest.mark.parametrize("workload", SERVE)
def test_every_limit_a_configuration_names_is_a_statistic(workload):
    """Every key is held, at the cell's size and at the rehearsal's: a sample
    of zeros holds them all, and the same limits with a key the comparison
    does not know raise instead of passing it by."""
    c = cells.find(workload)["config_values"]
    for limits in (c["limits"], c["rehearsal"]["limits"]):
        assert "served_logit_gap" in limits and all(callable(closed_loop.statistic(k)) for k in limits)
        assert closed_loop.judge(np.zeros(10), limits)[1] is True
        with pytest.raises(KeyError, match="served_logit_gap_mean"):
            closed_loop.judge(np.zeros(10), dict(limits, served_logit_gap_mean=0.1))


@pytest.mark.parametrize("workload", SERVE)
def test_an_empty_sample_is_not_correct(workload):
    numbers, ok = closed_loop.judge(np.zeros(0), LIMITS[workload])
    assert ok is False and all(value == 0.0 for value, _ in numbers.values())
    with pytest.raises(KeyError):
        closed_loop.judge(np.zeros(0), {"served_logit_gap_mean": 1.0})


@pytest.mark.parametrize("key,reads", [("served_logit_gap", 1.0), ("served_logit_gap_p99", 0.99),
                                       ("served_logit_gap_p97", 0.97), ("served_logit_gap_p50", 0.5)])
def test_a_key_names_the_maximum_or_a_percentile(key, reads):
    gaps = np.arange(1001, dtype=np.float32) / 1000.0
    assert closed_loop.statistic(key)(gaps) == pytest.approx(reads)


@pytest.mark.parametrize("key", ["served_logit_gap_p100", "served_logit_gap_p0", "served_logit_gap_p", "served_logit_gap_p9x",
                                 "served_logit_gap_p99.5", "served_logit_gap_max", "loss_abs"])
def test_a_key_that_names_no_statistic_is_an_error(key):
    with pytest.raises(KeyError, match="no statistic"):
        closed_loop.statistic(key)


I0 = 100  # the window's first iteration in the made-up runs


def _submitted(n: int, hits=(), warm_in: int = 5):
    """``warm_in`` requests submitted before the window opens and ``n`` from
    then on, in submit order, lengths that differ; the sixth of the window is
    the longest."""
    recs = []
    for i in range(-warm_in, n):
        req = types.SimpleNamespace(prompt=np.zeros(100 + 37 * (i % 7) + (900 if i == 5 else 0), np.int32),
                                    tokens=[0] * (20 + i % 5), prefix_hit=64 if i in hits else 0)
        recs.append(types.SimpleNamespace(session=40 + i, ask=0, client=(i * 5) % 16, req=req,
                                          submit_iter=I0 + 3 * i))
    return recs


def _keys(recs):
    return [(r.session, r.ask) for r in recs]


def _pick(recs, n, seed, finished=lambda r: True):
    return closed_loop.pick_sample(recs, n, seed, I0, finished)


@pytest.mark.parametrize("n", [3, 4, 6])
def test_the_sample_does_not_move_when_a_faster_run_finishes_more_behind_the_candidates(n):
    """The candidates are the first requests submitted in the window, the
    same at every pace: what a faster run submits and finishes behind them
    changes neither the sample nor the other candidates, and nothing
    submitted during warm-in is ever compared."""
    k = closed_loop.CANDIDATES * n
    slow, fast = _submitted(k + 2), _submitted(k + 40)
    for seed in (0, 1, 2147484356):
        sample, others = _pick(slow, n, seed)
        again, others_again = _pick(fast, n, seed)
        assert _keys(sample) == _keys(again) and _keys(others) == _keys(others_again)
        assert len(sample) == n and len(others) == k - n
        assert _keys(sample)[0] == (45, 0), "the longest candidate comes first"
        assert not set(_keys(sample)) & set(_keys(others))
        assert set(_keys(sample + others)) == {(40 + i, 0) for i in range(k)}


@pytest.mark.parametrize("late", [1, 7, 13, 20])
def test_a_candidate_that_only_a_faster_run_finishes_moves_no_other(late):
    """The slower side does not finish one candidate: it is missing from its
    sample, and the others keep the places the seed gave them, so the two
    sides share all but one request."""
    recs = _submitted(40)
    for seed in range(6):
        fast, _ = _pick(recs, 3, seed)
        slow, _ = _pick(recs, 3, seed, finished=lambda r: r.session != 40 + late)
        assert (40 + late, 0) not in _keys(slow)
        assert len(set(_keys(fast)) - set(_keys(slow))) <= 1
        if (40 + late, 0) not in _keys(fast):
            assert _keys(fast) == _keys(slow)


def test_the_longest_finished_candidate_is_compared():
    recs = _submitted(40)
    for seed in range(4):
        sample, _ = _pick(recs, 3, seed, finished=lambda r: r.session != 45)
        assert (45, 0) not in _keys(sample)
        total = lambda r: len(r.req.prompt) + len(r.req.tokens)
        assert total(sample[0]) == max(total(r) for r in recs[5:29] if r.session != 45)


def test_the_sample_is_drawn_from_the_seed():
    recs = _submitted(60)
    picks = {tuple(_keys(_pick(recs, 3, seed)[0])) for seed in range(12)}
    assert len(picks) > 6
    assert all(p[0] == (45, 0) for p in picks)


@pytest.mark.parametrize("hits", [tuple(i for i in range(32) if i != 9), (11,)])
def test_one_that_hit_the_prefix_cache_and_one_that_did_not(hits):
    """The re-ask cell's case: where the candidates hold both, the sample has
    both, whichever is rare."""
    recs = _submitted(60, hits=hits + tuple(range(32, 60)))
    for seed in range(8):
        sample, others = _pick(recs, 4, seed)
        assert len(sample) == 4 and {bool(r.req.prefix_hit) for r in sample} == {True, False}
        assert not set(_keys(sample)) & set(_keys(others))


@pytest.mark.parametrize("finished", [0, 1, 2])
def test_fewer_finished_than_asked_for_compares_what_there_is(finished):
    recs = _submitted(30)
    done = {40 + 4 * i for i in range(finished)}
    sample, others = _pick(recs, 3, 7, finished=lambda r: r.session in done)
    assert sorted(_keys(sample)) == sorted((s, 0) for s in done) and others == []


@pytest.mark.parametrize("submitted", [0, 2])
def test_a_window_shorter_than_its_candidates_compares_those_it_has(submitted):
    sample, others = _pick(_submitted(submitted), 3, 7)
    assert len(sample) == submitted and others == []
