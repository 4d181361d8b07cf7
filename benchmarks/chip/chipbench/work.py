"""The work each model step needed, rebuilt from what the loop logged:
each request's prompt, the step that admitted it, and the steps that
emitted its tokens, under the chunked-prefill rule of the configuration
(``chunk`` prompt tokens per step until the prompt is done, then one
token per step).

Only valid tokens count: a step that pads a slot, or holds idle slots,
needed nothing for them.  Where the logs do not fit the rule (a
preemption, a changed schedule) the steps are not rebuilt and the
metrics that read them report nothing."""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional


@dataclasses.dataclass
class StepWork:
    index: int
    prefill: bool            # some sequence fed prompt tokens
    tokens: int              # valid tokens fed
    sampled: int             # positions sampled
    flops: float
    bytes: float
    contexts: List[int]      # positions each sequence holds after it


def rebuild(served, model, dims, chunk: int) -> Optional[Dict[int, StepWork]]:
    """{step index: StepWork} for every step the loop ran, or None."""
    per_step: Dict[int, list] = {s.index: [] for s in served.steps}
    for log in served.logs.values():
        if log.admit_step is None or not log.steps:
            continue
        p = len(log.req.prompt)
        a = log.admit_step
        n_pre = -(-p // chunk)
        if log.steps[0] != a + n_pre - 1 or any(
                b != s + 1 for s, b in zip(log.steps, log.steps[1:])):
            return None
        feed = [(a + j, min(chunk, p - chunk * j), chunk * j, True)
                for j in range(n_pre)]
        feed += [(s, 1, p + j, False)
                 for j, s in enumerate(log.steps[1:])]
        emitted = set(log.steps)
        for s, n, before, pre in feed:
            if s in per_step:
                per_step[s].append((n, before, pre, s in emitted))
    out = {}
    for s, rows in per_step.items():
        flops = 0.0
        for n, before, _, sampled in rows:
            keys = model.attention_context(before, n)
            flops += model.token_flops(dims, 0, False) * n
            flops += (dims.layers * 4 * dims.heads * dims.head_dim * keys)
            if sampled:
                flops += model.token_flops(dims, 0, True) \
                    - model.token_flops(dims, 0, False)
        contexts = [before + n for n, before, _, _ in rows]
        sampled = sum(1 for r in rows if r[3])
        out[s] = StepWork(s, any(r[2] for r in rows),
                          sum(r[0] for r in rows), sampled, flops,
                          model.step_bytes(dims, contexts, sampled),
                          contexts)
    return out
