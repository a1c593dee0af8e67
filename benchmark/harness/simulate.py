"""Traffic generator: ZMWs sampled from the Arrow pair-HMM itself.

A vectorised copy of the program's `pbccs_tpu/simulate.py` (same model,
same edge conditions: both template ends are pinned to a match), on the
benchmark's own copy of the P6-C4 constants.  The draws differ from the
program's sampler (that one walks the chain base by base, about 0.06 s a
2 kb read; this one draws every position's stay count at once), so a seed
gives other reads than `chip_smoke.py` makes from it, from the same
distribution.

Every ZMW has a generator of its own, `default_rng([seed, stream, z])`:
what it holds does not depend on how many others were made, or in which
order, and every draw is independent.  A library (a configuration file's
`library` block) says how the insert length, the pass count and the SNR
are drawn.
"""

from __future__ import annotations

import hashlib
import math

import numpy as np

from . import p6c4

MOVIE = "m140905_042212_sidney_c100564852550000001823085912221377_s1_X0"


def draw(rng: np.random.Generator, spec: dict, size=None):
    """One draw from a distribution named in a data file."""
    kind = spec["dist"]
    if kind == "fixed":
        return np.full(size, spec["value"]) if size else spec["value"]
    if kind == "uniform":
        return rng.uniform(spec["lo"], spec["hi"], size)
    if kind == "uniform_int":
        return rng.integers(spec["lo"], spec["hi"] + 1, size)
    if kind == "lognormal":
        v = rng.lognormal(math.log(spec["median"]), spec["sigma"], size)
        return np.clip(v, spec.get("min", 0.0), spec.get("max", np.inf))
    if kind == "choice":
        return rng.choice(spec["values"], size, p=spec.get("weights"))
    raise ValueError(f"unknown distribution {kind!r}")


def sample_read(rng: np.random.Generator, tpl: np.ndarray,
                track: np.ndarray) -> np.ndarray:
    """One read of `tpl` from the pair-HMM with per-position moves `track`."""
    n = len(tpl) - 1
    nxt = tpl[1:].astype(np.int64)
    p = track[:-1]
    stay = p[:, p6c4.BRANCH] + p[:, p6c4.STICK]
    n_stay = rng.geometric(1.0 - stay) - 1
    advance = rng.random(n) < p[:, p6c4.MATCH] / (p[:, p6c4.MATCH] + p[:, p6c4.DARK])
    advance[-1] = True                    # the last base cannot be deleted
    counts = n_stay + advance
    total = int(counts.sum())
    step = np.repeat(np.arange(n), counts)
    is_match = np.zeros(total, bool)
    is_match[np.cumsum(counts)[advance] - 1] = True
    base = nxt[step]
    branch = rng.random(total) < (p[:, p6c4.BRANCH] / stay)[step]
    miscall = rng.random(total) < p6c4.PR_MISCALL
    other = (base + rng.integers(1, 4, total)) % 4
    body = np.where(np.where(is_match, miscall, ~branch), other, base)
    first = int(tpl[0])
    if rng.random() < p6c4.PR_MISCALL:
        first = (first + int(rng.integers(1, 4))) % 4
    return np.concatenate([[first], body]).astype(np.int8)


def make_zmw(seed: int, stream: int, z: int, library: dict) -> dict:
    """ZMW `z` of stream `stream`: template, subreads on alternating
    strands (a SMRTbell's passes), per-channel SNR."""
    rng = np.random.default_rng([seed, stream, z])
    length = int(draw(rng, library["insert_length"]))
    n_passes = int(draw(rng, library["passes"]))
    snr = np.asarray(draw(rng, library["snr"], 4), np.float64)
    tpl = rng.integers(0, 4, length).astype(np.int8)
    table = p6c4.transition_table(snr)
    strands = (tpl, p6c4.revcomp(tpl))
    tracks = [p6c4.transition_track(t, table) for t in strands]
    reads = [sample_read(rng, strands[k % 2], tracks[k % 2])
             for k in range(n_passes)]
    if library.get("partial_ends") and n_passes >= 2:
        # a polymerase read starts and stops anywhere in an insert
        reads[0] = reads[0][int(rng.uniform(0, 0.9) * len(reads[0])):]
        reads[-1] = reads[-1][:max(1, int(rng.uniform(0.1, 1) * len(reads[-1])))]
    return {"hole": z, "template": tpl, "reads": reads, "snr": snr}


def make_zmws(seed: int, stream: int, first: int, count: int,
              library: dict) -> list[dict]:
    return [make_zmw(seed, stream, z, library)
            for z in range(first, first + count)]


def digest(zmws: list[dict]) -> str:
    """A fingerprint of generated ZMWs (the tests pin one)."""
    h = hashlib.sha256()
    for z in zmws:
        h.update(np.int64(z["hole"]).tobytes())
        h.update(z["template"].tobytes())
        for r in z["reads"]:
            h.update(r.tobytes())
        h.update(np.asarray(z["snr"], np.float64).tobytes())
    return h.hexdigest()
