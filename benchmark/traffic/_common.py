"""Shared pieces of the traffic generators: seeded lengths and texts.

Lengths are *stratified*: a mix of n requests gets the n equally spaced
quantiles of its distribution, and the seed only shuffles which request gets
which. Every seed then offers the same amount of work, so the run-to-run
spread measures the system and not the luck of the draw (a median of 200
independent log-normal draws with sigma 0.9 alone moves by 9%).
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np

WORDS = ("the of and to in is that for it as was with be by on not he this "
         "are or his from at which but have an had they you were their one "
         "all we can her has there been if more when will would who so no "
         "out up said what its about than into them only other time new "
         "some could these two may first then do any like my now over such "
         "our man me even most made after also did many before must through "
         "years where much your way well down should because each just "
         "those people how too little state good very make world still own "
         "see men work long get here between both life being under never").split()


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream...)."""
    return np.random.Generator(np.random.PCG64([int(seed), *map(int, stream)]))


def stratified_lengths(n: int, spec: Dict) -> np.ndarray:
    """n lengths at the quantiles (i + 0.5) / n of `spec`:
    {"dist": "lognormal", "median", "sigma", "min", "max"} or
    {"dist": "uniform", "min", "max"}; clipped to [min, max], ascending."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in q])
        raw = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        raw = spec["min"] + q * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(int)


def shuffled_lengths(rng: np.random.Generator, n: int, spec: Dict) -> List[int]:
    return [int(x) for x in rng.permutation(stratified_lengths(n, spec))]


def text(rng: np.random.Generator, n_bytes: int, head: str = "") -> str:
    """ASCII text of exactly n_bytes, starting with `head`."""
    if n_bytes <= len(head):
        return head[:n_bytes]
    words = rng.choice(len(WORDS), size=n_bytes // 3 + 2)
    body = " ".join(WORDS[i] for i in words)
    return (head + body)[:n_bytes]


def prompt_of(rng: np.random.Generator, n_tokens: int, salt: str) -> str:
    """A prompt the served path tokenises to exactly n_tokens: the byte
    tokenizer adds one BOS and then one id per byte, so n_tokens - 1 ASCII
    bytes. The salt leads, so that no two prompts share their first KV
    block and the prefix cache finds nothing to reuse."""
    return text(rng, n_tokens - 1, head=salt + " ")


def request(prompt: str, max_tokens: int, **extra) -> Dict:
    return {"prompt": prompt, "prompt_tokens": len(prompt) + 1,
            "max_tokens": int(max_tokens), **extra}
