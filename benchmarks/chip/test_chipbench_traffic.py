"""The traffic generator: the same seed gives the same inputs, the mix
comes out as its table says, and the cumulative-weight Zipf sampler draws
exactly what ``random.choices(weights=...)`` draws."""
import collections
import json
import math
import random
from pathlib import Path

import pytest

from cellspec import load_traffic
from nsplan import NamespacePlan
from run_cell import WORK_SEED
from workgen import TrafficGenerator, ZipfSampler

HERE = Path(__file__).resolve().parent


def _cfg(name, trees=4):
    cfg = json.loads((HERE / "configs" / f"{name}.json").read_text())
    for p in cfg["namespace"]["parts"]:
        p["trees"] = min(p["trees"], trees)
    return cfg


def _gen(cfg, traffic):
    plan = NamespacePlan(cfg["namespace"]["parts"])
    return TrafficGenerator(plan, traffic["mix"], cfg["popularity"]["zipf_s"],
                            cfg["popularity"]["seed"])


def _traffic(name):
    return load_traffic(name)


@pytest.mark.parametrize("cfg,traffic", [
    ("spotify-1m", "spotify-table1-steady"),
    ("spotify-1m", "spotify-table1-saturate")])
def test_same_seed_same_inputs(cfg, traffic):
    c, t = _cfg(cfg), _traffic(traffic)
    seed = 2 ** 31 + 12345
    a = _gen(c, t).schedule(str(seed), 500.0, 2.0)
    b = _gen(c, t).schedule(str(seed), 500.0, 2.0)
    other = _gen(c, t).schedule(str(seed + 1), 500.0, 2.0)
    assert a[0] == b[0] and a[1] == b[1]
    assert a[0] != other[0] and a[1] != other[1]


def test_arrivals_are_poisson_at_the_rate():
    c, t = _cfg("spotify-1m"), _traffic("spotify-table1-steady")
    due, ops = _gen(c, t).schedule("7", 2000.0, 10.0)
    assert len(due) == len(ops)
    # 20,000 expected arrivals: within 5 standard deviations
    assert abs(len(due) - 20000) < 5 * math.sqrt(20000)
    gaps = [b - a for a, b in zip(due, due[1:])]
    mean = sum(gaps) / len(gaps)
    assert abs(mean - 1 / 2000) < 0.03 / 2000


@pytest.mark.parametrize("seed", [99, 2 ** 31 + 99])
def test_mix_histogram_matches_table(seed):
    c, t = _cfg("spotify-1m"), _traffic("spotify-table1-steady")
    g = _gen(c, t)
    g.rng = random.Random(seed)
    n = 40000
    names = collections.Counter()
    total = sum(m[1] for m in t["mix"])
    for _ in range(n):
        names[g.ops[__import__("bisect").bisect(
            g.cum, g.rng.random() * g.cum[-1], 0, len(g.ops) - 1)]] += 1
    for op, pct, _ in t["mix"]:
        p = pct / total
        sd = math.sqrt(n * p * (1 - p))
        assert abs(names[op] - n * p) <= 5 * sd + 1, op


def test_mix_ops_build_their_registry_ops():
    c, t = _cfg("spotify-1m"), _traffic("spotify-table1-steady")
    _, ops = _gen(c, t).schedule("3", 1000.0, 5.0)
    seen = {o.op for o in ops}
    from repro.core.ops_registry import REGISTRY
    assert seen <= set(REGISTRY.names())
    assert {"read", "ls", "stat", "create", "add_block"} <= seen


def test_zipf_sampler_matches_direct_weights():
    n, s = 5000, 1.1
    z = ZipfSampler(n, s)
    weights = [1.0 / (r + 1) ** s for r in range(n)]
    a, b = random.Random(5), random.Random(5)
    ranks = range(n)
    for _ in range(5000):
        assert z.draw(a) == b.choices(ranks, weights=weights, k=1)[0]


def test_traffic_paths_exist_in_the_plan():
    c, t = _cfg("spotify-1m"), _traffic("spotify-table1-steady")
    g = _gen(c, t)
    plan = g.plan
    for _ in range(2000):
        f = g.sample_file()
        assert plan.lookup(tuple(x for x in f.split("/") if x)) is False
        d = g.sample_dir()
        assert plan.lookup(tuple(x for x in d.split("/") if x)) is True


def test_work_seed_deals_the_same_work_in_another_order():
    c, t = _cfg("spotify-1m"), _traffic("spotify-table1-steady")
    a_due, a_ops = _gen(c, t).schedule("11", 500.0, 10.0,
                                       work_seed=WORK_SEED)
    b_due, b_ops = _gen(c, t).schedule("12", 500.0, 10.0,
                                       work_seed=WORK_SEED)
    key = lambda o: (o.op, o.path, o.path2, sorted(o.args.items()))  # noqa
    assert sorted(map(key, a_ops)) == sorted(map(key, b_ops))
    assert [key(o) for o in a_ops] != [key(o) for o in b_ops]
    assert a_due[-1] == pytest.approx(b_due[-1]) and a_due[-1] < 10.0
    gaps = lambda d: sorted(round(y - x, 9) for x, y in zip([0.0] + d, d))  # noqa
    assert gaps(a_due) == gaps(b_due)
    again = _gen(c, t).schedule("11", 500.0, 10.0, work_seed=WORK_SEED)
    assert again[0] == a_due and [key(o) for o in again[1]] == \
        [key(o) for o in a_ops]


def test_spotify_mix_is_the_papers_table1():
    mix = {op: (pct, on_dir) for op, pct, on_dir
           in _traffic("spotify-table1-steady")["mix"]}
    assert sum(p for p, _ in mix.values()) == pytest.approx(100.0)
    assert mix["read"] == (68.73, 0.0)
    assert mix["stat"][0] == 17.0 and mix["ls"][0] == 9.0
    assert mix["create"][0] == 1.2 and mix["content_summary"][0] == 0.01
