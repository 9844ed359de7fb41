"""The generator fixes the work of a run; the seed only reorders it."""

import os
import sys
import statistics

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench.lib import traffic  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(HERE, "traffic"))
               if f.endswith(".json"))


def mix_for(name):
    return traffic.load_mix(name)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    mix = mix_for(name)
    a = traffic.schedule(mix, 3000000019, 20.0, 1000)
    b = traffic.schedule(mix, 3000000019, 20.0, 1000)
    assert [(r.due_s, r.prompt, r.output_len) for r in a] == \
        [(r.due_s, r.prompt, r.output_len) for r in b]


@pytest.mark.parametrize("name", MIXES)
def test_any_seed_same_work(name):
    mix = mix_for(name)
    want = traffic.totals(traffic.schedule(mix, 1, 20.0, 1000))
    assert want["window"]["requests"] == round(mix["rate_rps"] * 20.0)
    for seed in (2, 77, 2 ** 31 + 11):
        assert traffic.totals(traffic.schedule(mix, seed, 20.0, 1000)) == want


def test_seed_changes_order_and_tokens():
    mix = mix_for("chat-steady")
    a = traffic.schedule(mix, 1, 20.0, 1000)
    b = traffic.schedule(mix, 2, 20.0, 1000)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert a[0].prompt != b[0].prompt


def test_phases_and_clipping():
    mix = mix_for("chat-steady")
    reqs = traffic.schedule(mix, 5, 30.0, 32768)
    for r in reqs:
        lo, length = traffic.phase_seconds(mix, 30.0)[r.phase]
        assert lo <= r.due_s < lo + length
        assert mix["prompt"]["min"] <= len(r.prompt) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= r.output_len <= mix["output"]["max"]
        assert all(1 <= t < 32768 for t in r.prompt)
    assert [r.index for r in reqs] == list(range(len(reqs)))
    assert reqs == sorted(reqs, key=lambda r: r.due_s)


def test_every_prompt_unique():
    mix = mix_for("chat-steady")
    reqs = traffic.schedule(mix, 9, 30.0, 32768)
    assert len({tuple(r.prompt[:32]) for r in reqs}) == len(reqs)


def test_arrivals_are_poisson_given_their_count():
    """Sorted uniforms: counts in equal bins are as dispersed as a Poisson
    process's (variance / mean near 1, less the 1/bins the fixed total
    takes), and no two seeds begin with the same requests."""
    mix = mix_for("chat-steady")
    bins, ratios, heads = 12, [], set()
    for seed in range(40):
        w = [r for r in traffic.schedule(mix, seed, 48.0, 1000, rate_rps=2.5)
             if r.phase == "window"]
        assert len(w) == 120
        counts = [0] * bins
        for r in w:
            counts[int(r.due_s / 4.0)] += 1
        ratios.append(statistics.pvariance(counts) / statistics.mean(counts))
        heads.add(tuple((len(r.prompt), r.output_len) for r in w[:4]))
    assert 0.75 < statistics.mean(ratios) < 1.1
    assert len(heads) == 40


def test_rate_override_scales_the_count():
    mix = mix_for("chat-steady")
    reqs = traffic.schedule(mix, 3, 10.0, 1000, rate_rps=5.0)
    assert sum(r.phase == "window" for r in reqs) == 50


def test_quantiles():
    q = traffic.lognormal_quantiles(
        {"median": 256, "sigma": 0.8, "min": 32, "max": 768}, 101)
    assert q[50] == 256 and q[0] >= 32 and q[-1] == 768
    assert q == sorted(q)


def test_prime_parity_and_schedule_share_no_prefix():
    """Generators seeded alike give one sequence at an offset; prompts that
    began on the same word would share a prefix and hit the prefix cache.
    Each purpose therefore has a stream of its own."""
    mix = mix_for("chat-steady")
    for seed in (1, 11, 101, 3000000019):
        heads = [tuple(r.prompt[:16])
                 for r in traffic.schedule(mix, seed, 30.0, 32768)]
        for purpose in ("prime", "parity"):
            rng = traffic.token_rng(purpose, seed)
            heads += [tuple(rng.randrange(1, 32768) for _ in range(16))
                      for _ in range(8)]
        assert len(set(heads)) == len(heads)


def test_a_mix_without_strata_is_scheduled_as_it_always_was():
    """``strata`` is a key a mix may set; the other mixes' schedules are
    the ones their cells' numbers were read on (pinned from the generator
    before the key existed)."""
    mix = mix_for("chat-steady")
    assert "strata" not in mix
    reqs = traffic.schedule(mix, 3000000019, 20.0, 1000)
    assert [(len(r.prompt), r.output_len) for r in reqs[:5]] == [
        (268, 93), (223, 177), (768, 256), (673, 39), (394, 78)]
    assert reqs[0].due_s == pytest.approx(-8.843133, abs=1e-6)
    for off in (0, 1):
        same = traffic.schedule(dict(mix, strata=off), 3000000019, 20.0, 1000)
        assert [(r.due_s, r.prompt) for r in same] == \
            [(r.due_s, r.prompt) for r in reqs]


@pytest.mark.parametrize("k, n", [(6, 61), (6, 12), (4, 10), (8, 8), (5, 3)])
def test_strata_every_k_arrivals_hold_one_length_of_each_band(k, n):
    """Whatever head of the queue a saturated replica gets to serve, it is
    the mix in small: each whole block of k consecutive arrivals has one
    prompt length and one output length from each of the k bands."""
    mix = dict(mix_for("longgen-overload-olmo"), strata=k, ramp_s=0.0,
               tail_s=0.0)
    window = 10.0
    orders = set()
    for seed in (1, 2, 3000000019, 2 ** 31 + 11):
        reqs = traffic.schedule(mix, seed, window, 1000, rate_rps=n / window)
        assert len(reqs) == n
        for what, dist in ((lambda r: len(r.prompt), mix["prompt"]),
                           (lambda r: r.output_len, mix["output"])):
            got = [what(r) for r in reqs]
            q = traffic.lognormal_quantiles(dist, n)
            assert sorted(got) == q
            bands = [q[i * n // k:(i + 1) * n // k] for i in range(k)]
            least = min(len(b) for b in bands)
            for b in range(least):
                block = sorted(got[b * k:(b + 1) * k])
                assert all(lo[0] <= x <= lo[-1]
                           for x, lo in zip(block, bands)), (seed, b)
        orders.add(tuple(len(r.prompt) for r in reqs))
    assert len(orders) == 4 or n < 4


def test_strata_steady_what_the_head_of_the_queue_asks_for():
    """The sum of the lengths of a queue's first half, over seeds: a few
    per cent of its mean with strata, about three times that without."""
    base = mix_for("longgen-overload-olmo")
    assert base["strata"] == 6

    def heads(mix):
        out = []
        for seed in range(40):
            w = [r for r in traffic.schedule(mix, seed, 51.0, 1000)
                 if r.phase == "window"]
            out.append(sum(len(r.prompt) for r in w[:len(w) // 2]))
        return statistics.pstdev(out) / statistics.mean(out)

    with_strata, without = heads(base), heads(dict(base, strata=0))
    assert with_strata < 0.05 < without
    assert without > 2 * with_strata


OVERLOAD = [n for n in MIXES if "overload" in n]


@pytest.mark.parametrize("name", OVERLOAD)
def test_every_overload_mix_offers_every_seed_the_same_head(name):
    """All seven overload mixes carry ``strata`` 6 (PR 49): at the mix's own
    rate and the benchmark's 51 s every six consecutive arrivals of a phase
    hold one prompt and one output length of each sextile of that phase's
    lengths; any two seeds have offered the same tokens after any whole
    number of sixes to within two of the mix's largest requests (the
    lengths differ inside a sextile, and the sum of that walks), and the
    same tokens exactly by the window's end."""
    mix = mix_for(name)
    assert len(OVERLOAD) == 7 and mix["strata"] == 6
    k, by_seed = 6, {}
    for seed in (1, 3000000019, 2 ** 31 + 11):
        reqs = traffic.schedule(mix, seed, 51.0, 1000)
        for phase in traffic.PHASES:
            rs = [r for r in reqs if r.phase == phase]
            n = len(rs)
            for what, dist in ((lambda r: len(r.prompt), mix["prompt"]),
                               (lambda r: r.output_len, mix["output"])):
                q = traffic.lognormal_quantiles(dist, n)
                bands = [q[i * n // k:(i + 1) * n // k] for i in range(k)]
                got = [what(r) for r in rs]
                assert sorted(got) == q
                for b in range(min(len(band) for band in bands)):
                    block = sorted(got[b * k:(b + 1) * k])
                    assert all(band[0] <= x <= band[-1]
                               for x, band in zip(block, bands)), (seed, b)
        by_seed[seed] = [r for r in reqs if r.phase == "window"]
    most = mix["prompt"]["max"] + mix["output"]["max"]
    (first, *others) = by_seed.values()
    for other in others:
        assert len(other) == len(first)
        for upto in range(k, len(first) + 1, k):
            a, b = (sum(len(r.prompt) + r.output_len for r in w[:upto])
                    for w in (first, other))
            assert abs(a - b) <= 2 * most, (name, upto)
        assert sum(len(r.prompt) + r.output_len for r in first) == \
            sum(len(r.prompt) + r.output_len for r in other)
