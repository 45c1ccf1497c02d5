import pytest

from bench import traffic

from conftest import ROOT, load

MIXES = ["azure-conv-closed8", "tiny-open"]


def mix(name):
    """A cell's mix (``bench/traffic``) or a test's (``tests/data``)."""
    import json

    path = ROOT / "bench" / "traffic" / f"{name}.json"
    return json.loads(path.read_text()) if path.exists() else load(f"{name}.json")


def _sig(plan):
    return [(len(r.prompt), r.max_new_tokens, r.due, r.seed, r.prompt.tobytes())
            for r in plan.requests]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_plan(name):
    a = traffic.make_plan(mix(name), vocab=1000, seed=2**31 + 12345, seconds=30)
    b = traffic.make_plan(mix(name), vocab=1000, seed=2**31 + 12345, seconds=30)
    c = traffic.make_plan(mix(name), vocab=1000, seed=2**31 + 12346, seconds=30)
    assert _sig(a) == _sig(b)
    assert _sig(a) != _sig(c)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work(name):
    """Every seed sends the same sizes (and gaps) in the same order; only
    the token ids and sampling seeds differ."""
    plans = [traffic.make_plan(mix(name), vocab=1000, seed=s, seconds=30)
             for s in (1, 2**33 + 7)]
    sizes = [[(len(r.prompt), r.max_new_tokens, r.due) for r in p.requests] for p in plans]
    assert sizes[0] == sizes[1]
    assert _sig(plans[0]) != _sig(plans[1])


@pytest.mark.parametrize("name,config", [("azure-conv-closed8",
                                          "codeqwen15-7b-ssa-packed-16l")])
def test_lm_mixes_stay_within_bounds(name, config):
    import json

    eng = json.loads((ROOT / "bench" / "configs" / f"{config}.json").read_text())["engine"]
    max_seq, page = eng["max_seq"], eng["page_size"]
    m = mix(name)
    plan = traffic.make_plan(m, vocab=92416, seed=99, seconds=51, max_total=max_seq - 2)
    for r in plan.requests:
        total = len(r.prompt) + r.max_new_tokens
        assert m["prompt"]["min"] <= len(r.prompt) <= m["prompt"]["max"]
        assert total <= max_seq - 2            # never cut short by max_seq
        assert -(-total // page) <= max_seq // page
        assert r.prompt.min() >= 0 and r.prompt.max() < 92416


def test_open_loop_requests_all_fall_due_in_the_window():
    m = mix("tiny-open")
    plan = traffic.make_plan(m, vocab=256, seed=5, seconds=20)
    dues = [r.due for r in plan.requests]
    assert len(dues) == round(m["rate"] * 20)
    assert all(0 < d < 20 for d in dues)
    assert dues == sorted(dues)


@pytest.mark.parametrize("seed", [3, 2**31 + 9])
def test_closed_loop_first_round_is_staggered(seed):
    """The k-th shortest first-round output keeps (k + 1) / 8 of its tokens,
    whatever the seed; later requests keep theirs whole."""
    m = mix("azure-conv-closed8")
    plan = traffic.make_plan(m, vocab=1000, seed=seed, seconds=30)
    sizes = traffic.quantile_sizes(m["output"], m["block"])
    first = sorted(r.max_new_tokens for r in plan.first_round())
    assert first == [-(-int(n) * (k + 1) // 8) for k, n in enumerate(sorted(sizes))]
    assert sorted(r.max_new_tokens for r in plan.requests[8:16]) == sorted(sizes)


def test_a_mix_that_could_cut_a_request_is_refused():
    m = load("tiny-open.json")
    with pytest.raises(ValueError, match="uncut"):
        traffic.make_plan(m, vocab=100, seed=1, seconds=1, max_total=10)
