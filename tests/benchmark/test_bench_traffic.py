"""Every serving mix is a replayed trace: the same for every ``--seed``."""

import numpy as np
import pytest

import cells
import manifest as M
import traffic_gen

SERVING = cells.by_driver("closed_loop")  # every served cell of the manifest, with its own mix and configuration
# a mix with a shared document in front of every ask of a session, at the
# rehearsal's size, beside the manifest's own
REASK_TINY = {"name": "reask-tiny", "driver": "closed_loop", "clients": 2, "trace_seed": 7, "trace_sessions": 64,
              "document_tokens": 96, "asks_per_session": 4, "start": {"cut_first_session": True},
              "prompt": {"dist": "uniform", "min": 8, "max": 16},
              "output": {"dist": "fixed", "value": 4, "min": 4, "max": 4}, "check_requests": 4}


@pytest.mark.parametrize("mix", SERVING + [REASK_TINY], ids=lambda m: m if isinstance(m, str) else m["name"])
def test_trace_is_a_function_of_the_file_alone(mix):
    traffic = mix if isinstance(mix, dict) else cells.find(mix)["traffic_values"]
    a, b = traffic_gen.expand(traffic), traffic_gen.expand(dict(traffic))
    assert a == b and len(a) == traffic["trace_sessions"]
    p, o = traffic["prompt"], traffic["output"]
    for s in a:
        assert s.document_len == traffic.get("document_tokens", 0)
        assert len(s.asks) == traffic.get("asks_per_session", 1)
        for ask in s.asks:
            assert p["min"] <= ask.prompt_len <= p["max"] and o["min"] <= ask.output_len <= o["max"]
    # token ids, and nothing else, come from --seed
    s = a[5]
    for seed in (1, 3_000_000_011):
        doc, ask = traffic_gen.document_tokens(seed, s, 32768), traffic_gen.ask_tokens(seed, s, 0, 32768)
        assert len(doc) == s.document_len and len(ask) == s.asks[0].prompt_len
        assert ask.min() >= 0 and ask.max() < 32768
    assert not np.array_equal(traffic_gen.ask_tokens(1, s, 0, 32768), traffic_gen.ask_tokens(2, s, 0, 32768))
    assert np.array_equal(traffic_gen.ask_tokens(2, s, 0, 32768), traffic_gen.ask_tokens(2, s, 0, 32768))


@pytest.mark.parametrize("workload", SERVING)
def test_mix_fits_the_configuration(workload):
    """The longest request fits a slot of the cell's own configuration, and
    the mix's medians are the issue's."""
    cell = cells.find(workload)
    s, traffic = cell["config_values"]["serving"], cell["traffic_values"]
    assert traffic_gen.longest_request(traffic, s["max_cache_len"]) <= s["max_cache_len"]
    assert traffic["clients"] <= s["num_slots"]
    if traffic["prompt"]["dist"] == "lognormal":
        lens = [a.prompt_len for x in traffic_gen.expand(traffic) for a in x.asks]
        assert abs(np.median(lens) / traffic["prompt"]["median"] - 1) < 0.1


def test_stagger_spreads_the_population():
    one = traffic_gen.Session(0, 0, (traffic_gen.Ask(100, 64),))
    outs = [traffic_gen.stagger(one, i, 4).asks[0].output_len for i in range(4)]
    assert outs == [64, 48, 32, 16]  # the first admitted keeps its whole output
    four = traffic_gen.Session(0, 96, tuple(traffic_gen.Ask(10 + i, 4) for i in range(4)))
    assert [len(traffic_gen.stagger(four, i, 8).asks) for i in range(8)] == [4, 3, 2, 1, 4, 3, 2, 1]


@pytest.mark.parametrize("workload,mix", [(SERVING[1], None), (SERVING[1], REASK_TINY)],
                         ids=lambda x: x if isinstance(x, str) else "" if x is None else x["name"])
def test_closed_loop_repeats_on_the_real_engine(workload, mix, optimized_xla):
    """Tiny widths through the real ServingEngine, twice with different
    seeds and no look at the clock: the same sequence of batch compositions
    and of prefill dispatches."""
    import types

    import run as R
    from spans import Spans

    closed_loop = M.load_driver("closed_loop")
    runs = []
    for seed in (11, 12):
        args = types.SimpleNamespace(seed=seed, seconds=1.0, trace=0, cpu_rehearsal=True, control=None)
        ctx = R.Context(cells.find(workload), args)
        if mix is not None:
            ctx.traffic = mix
        engine = closed_loop.build_engine(ctx)
        loop = closed_loop.ClosedLoop(engine, ctx.traffic, seed, ctx.arch, ctx.settings, Spans())
        for _ in range(40):
            loop.iterate()
        runs.append(([it["comp"] for it in loop.iters], [it["prefill"] for it in loop.iters],
                     [r.req.prefix_hit for r in loop.recs]))
        assert all(r.req.outcome in (None, "finished") for r in loop.recs)
    assert runs[0] == runs[1]
    assert any(runs[0][1]) and any(len(c) > 1 for c in runs[0][0])
    if ctx.traffic.get("document_tokens"):
        assert sum(h > 0 for h in runs[0][2]) >= len(runs[0][2]) // 2
