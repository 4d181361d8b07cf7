"""Is what the timed path served correct?  A sample of the served
requests, drawn from the seed with the longest among them, is run
through the plain reference once the program's state is freed; for
every served token the reference reads how far that token's logit lies
below its own best (0 where the reference agrees with the choice).  A
greedy server that rounds differently from the reference lands only on
near-ties, so the widest such gap stays small; a wrong token, a lost KV
write or a precision below the configuration's lands far below.

The control (``control=True``) puts the reference in the program's
place one precision below the one the configuration states (matmul
operands in float8 e4m3 for bf16 compute): at each position of the same
prompts and served tokens it reads the gap of the token that the control
puts first, and those gaps go through the same verdict."""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def sample(logs, seed: int, target_tokens: int, max_requests: int,
           after: float) -> List:
    """The finished requests the check reads, of those that ended after
    ``after`` (the window's opening): the one with the most served
    tokens, then others in an order drawn from ``seed`` until
    ``target_tokens`` tokens or ``max_requests`` requests."""
    cands = sorted((log for log in logs.values() if log.tokens
                    and log.finished and log.times[-1] > after),
                   key=lambda log: log.rid)
    if not cands:
        return []
    longest = max(cands, key=lambda log: len(log.tokens))
    rest = [c for c in cands if c is not longest]
    rng = np.random.default_rng([int(seed), 7])
    picks, total = [longest], len(longest.tokens)
    for i in rng.permutation(len(rest)):
        if total >= target_tokens or len(picks) >= max_requests:
            break
        picks.append(rest[i])
        total += len(rest[i].tokens)
    return picks


def gaps(model, dims, weights, picks, pad_to: int,
         control: bool = False) -> Dict[str, np.ndarray]:
    """Per served token of ``picks``: ``served`` = the reference's best
    logit minus its logit of the served token; with ``control`` also
    ``control`` = the same gap for the token the control puts first."""
    out = {"served": [], "control": []}
    for log in picks:
        prompt, toks = list(log.req.prompt), list(log.tokens)
        seq = prompt + toks[:-1]
        read = [len(prompt) - 1 + j for j in range(len(toks))]
        ids = np.asarray(toks, np.int32)[:, None]
        if control:
            _, _, top_c = model.reference_scores(
                dims, weights, seq, read, ids, pad_to=pad_to, control=True)
            ids = np.concatenate([ids, top_c[:, None].astype(np.int32)], 1)
        best, scored, _ = model.reference_scores(dims, weights, seq, read,
                                                 ids, pad_to=pad_to)
        out["served"].append(best - scored[:, 0])
        if control:
            out["control"].append(best - scored[:, 1])
    return {k: np.concatenate(v) if v else np.zeros(0)
            for k, v in out.items()}


def verdict(served_gaps: np.ndarray, limits: Dict) -> Dict:
    """The numbers compared, each with its limit; ``ok`` when every one
    holds.  A non-finite gap fails."""
    n = int(served_gaps.size)
    worst = float(served_gaps.max()) if n else float("inf")
    if n and not np.isfinite(served_gaps).all():
        worst = float("inf")
    checks = {
        "max_logit_gap": {"value": worst,
                          "limit": float(limits["max_logit_gap"])},
        "checked_tokens": {"value": n,
                           "limit": int(limits["min_checked_tokens"])},
    }
    ok = (worst <= checks["max_logit_gap"]["limit"]
          and n >= checks["checked_tokens"]["limit"])
    return {"ok": bool(ok), "checks": checks}
