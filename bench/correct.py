"""How ``correct`` is decided: served tokens against the plain reference.

After the window closes, a sample of the finished requests (drawn from the
seed, always with the longest one in it) is run through the float32
reference (``reference.py``) over each prompt followed by its served tokens.
At every served position the reference's best logit minus its logit for the
token the engine served is that token's gap: 0 where the engine's greedy
token is the reference's too, small where rounding let a near-tie go the
other way, and a leaf's output wide where rounding sent a token down
another leaf of a tree.  The number compared is the mean gap over every
served position of the sample, ``mean_logit_gap``, against the cell's limit
in ``bench/limits/<cell>.json``; the widest gap and the share of positions
whose token is not the reference's best are read beside it.  PERF.md gives
why the mean is compared and the readings each limit was set from.

The control (``control=True``, run by ``calibrate.py``, never by a
benchmark run) is the same reference computed in float8 e4m3 put in the
program's place: at each position of the same prompts and served tokens,
the token the float8 reference ranks first is scored by the float32
reference, and the same numbers and limits decide its ``correct``.
"""
from __future__ import annotations

import types

import numpy as np

from bench import reference


def finished(window) -> list:
    return [s for s in window.requests.values()
            if s.tokens is not None and len(s.tokens) == s.max_new_tokens]


def sample(window, seed: int, k: int) -> list:
    """k finished requests: the longest (prompt + served), and k - 1 more
    drawn from the seed."""
    done = sorted(finished(window), key=lambda s: s.rid)
    if not done:
        return []
    longest = max(done, key=lambda s: (len(s.prompt) + len(s.tokens), s.rid))
    rest = [s for s in done if s is not longest]
    rng = np.random.default_rng([int(seed), 11])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def token_gaps(params, config, served, s_pad: int, quant: str = ""):
    """Per served position: (gap of the served token, gap of the token the
    ``quant`` reference ranks first, both scored by the float32 reference;
    the reference's routing margin there).  With ``quant=""`` the second
    is 0."""
    seq = np.concatenate([served.prompt, served.tokens[:-1]]).astype(np.int32)
    P = len(served.prompt)
    rows = np.arange(P - 1, P - 1 + len(served.tokens))
    ref, margin = reference.logits_at(params, config, seq, rows, s_pad,
                                      margins=True)
    best = ref.max(axis=-1)
    got = ref[np.arange(len(rows)), served.tokens]
    if not quant:
        return best - got, np.zeros_like(best), margin
    low = reference.logits_at(params, config, seq, rows, s_pad, quant=quant)
    pick = ref[np.arange(len(rows)), low.argmax(axis=-1)]
    return best - got, best - pick, margin


#: the numbers a cell's limits file may hold, each a reduction of the
#: gaps of every served position of the sample
NUMBERS = {
    "max_logit_gap": lambda g: float(g.max()),
    "mean_logit_gap": lambda g: float(g.mean()),
    # share of served positions whose token is not the reference's best
    "off_best_share": lambda g: float((g > 0).mean()),
}


def numbers(gaps: np.ndarray) -> dict:
    return {name: f(gaps) for name, f in NUMBERS.items()}


def check(params, config, window, seed: int, k: int, max_len: int,
          limits: dict, control: bool = False) -> types.SimpleNamespace:
    """Decide ``correct`` for the window's served tokens, or with
    ``control`` for the float8 control at the same positions.  Returns
    ``ok``; ``checks`` ({name: {"value", "limit"}} for each number the
    cell's limits file holds); ``got`` (every number read); ``n`` (served
    positions compared); and the per-position ``program`` and ``control``
    gaps and routing ``margin`` of the sample.  No limits, no sample or a
    number above its limit: not correct."""
    picked = sample(window, seed, k)
    s_pad = reference.padded_len(max_len)
    parts = [token_gaps(params, config, s, s_pad,
                        quant="fp8" if control else "") for s in picked]
    program = ctrl = margin = np.zeros(0)
    if parts:
        program, ctrl, margin = (np.concatenate(x) for x in zip(*parts))
    gaps = ctrl if control else program
    ok, checks, got = verdict(gaps, limits)
    return types.SimpleNamespace(ok=ok, checks=checks, got=got,
                                 n=int(gaps.size), program=program,
                                 control=ctrl, margin=margin)


def verdict(gaps: np.ndarray, limits: dict) -> tuple[bool, dict, dict]:
    """(correct, checks, every number read) of per-position gaps."""
    got = numbers(gaps) if gaps.size else {}
    checks = {name: {"value": got.get(name), "limit": limit}
              for name, limit in sorted(limits.items())}
    ok = bool(checks) and all(
        c["value"] is not None and np.isfinite(c["value"])
        and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks, got
