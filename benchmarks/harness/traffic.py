"""One general traffic generator; a mix is a file of parameters.

A mix has ``rooms`` closed-loop callers of ``content_backend.generate``
and, where ``guesses`` is given, an open loop of ``similarity`` calls. A
room opens a new story, from a title of ``seed_file``, every
``story_rounds`` rounds (the game's ``episodes_per_story``) and seeds the
rounds between from its previous text, as ``RoundManager`` does.
Every seed gets the same multiset of arrival gaps and the same number of
phrase calls, in another order, so the seed does not change the work.
"""

from __future__ import annotations

import math
import os

import numpy as np

from .manifest import ROOT


def lines(path: str) -> list:
    with open(os.path.join(ROOT, path)) as f:
        return [ln.strip() for ln in f if ln.strip()]


def story_title(mix: dict, seed: int, room: int, story: int) -> str:
    """The title that opens a room's story number ``story`` (from 0): the
    rooms walk the seed file together from an offset the seed gives, so
    every seed sends the same titles in another order."""
    titles = lines(mix["seed_file"])
    return titles[(seed + room + mix["rooms"] * story) % len(titles)]


def guess_schedule(mix: dict, seed: int, horizon_s: float):
    """(answers per room, calls): each call is (offset_s, room, guess,
    on_device). Offsets follow Poisson arrivals at the mix's rate: the
    exponential distribution's quantiles as gaps, shuffled by the seed."""
    g = mix.get("guesses")
    if not g:
        return [], []
    rng = np.random.RandomState(seed % (2 ** 32))
    words = lines(g["wordlist"])
    answers = [[words[i] for i in rng.choice(len(words), g["pairs_per_call"],
                                             replace=False)]
               for _ in range(mix["rooms"])]
    n = int(round(g["rate_per_s"] * horizon_s))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / g["rate_per_s"]
    rng.shuffle(gaps)
    offsets = np.cumsum(gaps)
    n_phrase = int(round(n * g["phrase_share"]))
    on_device = np.zeros(n, bool)
    on_device[:n_phrase] = True
    rng.shuffle(on_device)
    seen, calls = set(), []
    for i in range(n):
        room = int(rng.randint(mix["rooms"]))
        if on_device[i]:
            while True:
                a, b = rng.randint(len(words), size=2)
                guess = f"{words[a]} {words[b]}"
                if guess not in seen:
                    seen.add(guess)
                    break
        else:
            guess = words[int(rng.randint(len(words)))]
        calls.append((float(offsets[i]), room, guess, bool(on_device[i])))
    return answers, calls


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of all values (no interpolation)."""
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]
