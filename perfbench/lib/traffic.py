"""One general traffic generator, driven by a mix file under ``traffic/``.

A mix fixes the WORK of a run; ``--seed`` decides only its order and timing.
For each phase (ramp before the window, the window, the tail after it) the
generator takes ``n = round(rate_rps * seconds)`` requests whose prompt and
output lengths are the ``n`` mid-quantiles ``(i + 0.5) / n`` of the mix's two
clipped log-normal distributions. The seed draws two permutations per phase
(which prompt length meets which output length, and the order the requests
arrive in), the token ids, and the arrival offsets: the sorted values of
``n`` uniform draws over the phase, which is a Poisson process conditioned on
its count. So every seed sends the same number of requests, prompt tokens and
output tokens, and the local bursts of a Poisson process stay.

Above capacity a run serves only the head of its queue, so WHICH lengths
arrive first is then work too, and the seed's. A mix may therefore set
``strata`` = k: each phase's sorted lengths are cut into k bands of equal
count, and every k consecutive arrivals hold one prompt length and one output
length from each band (which of a band's, their pairing and their order
within the k: the seed's). Any head of the queue is then the mix in small,
on every seed. Without the key both permutations are uniform, as before.

Mix keys: ``rate_rps`` (requests a second), ``ramp_s``,
``tail_s``, ``prompt`` and ``output`` (``{"median", "sigma", "min",
"max"}``), and optionally ``strata``. Every prompt is unique.
"""

from __future__ import annotations

import json
import math
import os
import random
from dataclasses import dataclass
from statistics import NormalDist
from typing import Any, Dict, List, Tuple

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ("ramp", "window", "tail")


@dataclass
class Request:
    index: int
    phase: str
    due_s: float                 # relative to the window's opening
    prompt: List[int]
    output_len: int


def load_mix(name: str) -> Dict[str, Any]:
    """The mix file. Its rate is a share of ONE configuration's knee: a cell
    on another configuration brings a mix file of its own."""
    path = os.path.join(HERE, "traffic", f"{name}.json")
    with open(path) as f:
        mix = json.load(f)
    for key in ("rate_rps", "ramp_s", "tail_s", "prompt", "output"):
        if key not in mix:
            raise ValueError(f"traffic mix {name}: missing {key!r}")
    mix["name"] = name
    return mix


def lognormal_quantiles(dist: Dict[str, float], n: int) -> List[int]:
    """The n mid-quantiles of a log-normal, clipped and rounded to ints."""
    nd = NormalDist()
    mu = math.log(dist["median"])
    out = []
    for i in range(n):
        x = math.exp(mu + dist["sigma"] * nd.inv_cdf((i + 0.5) / n))
        out.append(int(round(min(max(x, dist["min"]), dist["max"]))))
    return out


def stratified(values: List[int], k: int, rng: random.Random) -> List[int]:
    """``values`` (sorted) in an order whose every k consecutive entries
    hold one from each of the k bands of equal count; the last entries come
    from the bands that have any left."""
    n = len(values)
    bands = [values[i * n // k:(i + 1) * n // k] for i in range(k)]
    for band in bands:
        rng.shuffle(band)
    out: List[int] = []
    while any(bands):
        block = [band.pop() for band in bands if band]
        rng.shuffle(block)
        out += block
    return out


def phase_seconds(mix: Dict[str, Any], window_s: float
                  ) -> Dict[str, Tuple[float, float]]:
    """phase -> (start, length), relative to the window's opening."""
    return {"ramp": (-float(mix["ramp_s"]), float(mix["ramp_s"])),
            "window": (0.0, float(window_s)),
            "tail": (float(window_s), float(mix["tail_s"]))}


def schedule(mix: Dict[str, Any], seed: int, window_s: float,
             vocab_size: int, rate_rps: float = 0.0) -> List[Request]:
    """Every request of a run, ordered by due time. ``rate_rps`` overrides
    the mix's rate (the knee sweep uses it)."""
    rate = rate_rps or float(mix["rate_rps"])
    reqs: List[Request] = []
    for phase, (start, length) in phase_seconds(mix, window_s).items():
        # a stream of its own per phase (and for the prime and parity
        # prompts, ``token_rng``): streams seeded alike are the same
        # sequence at an offset, and two prompts that began on the same
        # word would share a prefix — a prefix-cache hit that removes work
        rng = token_rng(phase, seed)
        n = int(round(rate * length))
        if n <= 0:
            continue
        plens = lognormal_quantiles(mix["prompt"], n)
        olens = lognormal_quantiles(mix["output"], n)
        strata = int(mix.get("strata") or 0)
        if strata > 1:
            plens = stratified(plens, strata, rng)
            olens = stratified(olens, strata, rng)
        else:
            rng.shuffle(plens)
            rng.shuffle(olens)
        dues = sorted(start + length * rng.random() for _ in range(n))
        for due, plen, olen in zip(dues, plens, olens):
            prompt = [rng.randrange(1, vocab_size) for _ in range(plen)]
            reqs.append(Request(0, phase, due, prompt, olen))
    reqs.sort(key=lambda r: r.due_s)
    for i, r in enumerate(reqs):
        r.index = i
    return reqs


def token_rng(purpose: str, seed: int) -> random.Random:
    """An independent generator for one purpose of one seed."""
    return random.Random(f"{purpose}:{seed}")


def totals(reqs: List[Request]) -> Dict[str, Any]:
    """What a schedule asks for, phase by phase: the numbers that must not
    depend on the seed."""
    out: Dict[str, Any] = {}
    for phase in PHASES:
        rs = [r for r in reqs if r.phase == phase]
        out[phase] = {
            "requests": len(rs),
            "prompt_tokens": sum(len(r.prompt) for r in rs),
            "output_tokens": sum(r.output_len for r in rs),
            "prompt_lens": sorted(len(r.prompt) for r in rs),
            "output_lens": sorted(r.output_len for r in rs),
        }
    return out
